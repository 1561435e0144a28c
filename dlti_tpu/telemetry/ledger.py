"""Goodput ledger & critical-path attribution: account every training
second and every request millisecond.

MegaScale's observability thesis (echoed in ``steplog.py`` /
``timeseries.py``) is that goodput at scale is *recovered by attribution*:
the framework itself must say where the time went, or recovery work
(restarts, rollbacks, tier restores, failovers) silently eats the wall
clock the throughput headline claims. This module is the two-sided
accounting layer:

**Training — :class:`GoodputLedger`, a phase clock.** At any instant the
run is in exactly one phase; every ``enter(phase)`` transition books the
elapsed interval to the *previous* phase's bucket, so bucket totals sum to
wall clock *by construction* (the conservation property is tier-1-tested,
not aspirational). The trainer transitions at the same sites its tracer
spans cover (data wait, host→device, step dispatch, device sync,
eval, checkpoint save/restore, sentinel rollback, SDC probe); after a
sentinel rollback the re-executed steps book to ``replay`` instead of
``step_compute`` (``begin_replay``/``end_replay``), so a drill that
converges still shows what fraction of the run was productive. The
elastic supervisor stitches per-generation worker ledgers across restarts
and adds the buckets only it can see: ``restart_downtime`` (teardown +
backoff + respawn gaps) and shrunk-world degradation
(:func:`stitch_ledgers`).

**Serving — per-request critical-path attribution.** The engine, gateway,
prefix tiers and failover paths already stamp monotonic timestamps on
each :class:`~dlti_tpu.serving.engine.Request`;
:func:`request_breakdown` assembles them into a phase breakdown
(``gateway_queue`` → ``queue`` → ``tier_restore`` → ``prefill`` →
``decode``, plus ``failover``/``preempt`` requeue stalls) that sums to
the client-observed latency. :class:`CriticalPathTracker` (one per
:class:`~dlti_tpu.telemetry.lifecycle.RequestTelemetry`, shared across
replicas) folds every finished request into the
``dlti_request_phase_seconds_total{phase=}`` exposition and retains the K
worst requests with their full timelines for ``GET /debug/slow`` — the
answer to "why was this p99 request slow: queue, prefill, tier restore,
or failover?".

**Serving — :class:`StepperAccount`, the stepper thread's phase clock.**
The same idea on the thread that drives ``engine.step()``: the thread is
in exactly one phase at any instant, an inner phase suspends the one round
it, and a transition books the time since the last one to the phase that
was open. It is always on (one clock read and one add a transition: what an
operator has is a scrape, not a profiler), its phases are the tracer's
spans under the same names through one helper
(:meth:`StepperAccount.phase`), and a host phase that stood still for
:data:`STEPPER_STALL_S` leaves a record. Beside it the collector's pauses
(:func:`install_gc_hook`).

Cost contract (same as the tracer): a *disabled* ledger's ``enter()`` is
one attribute read + an early return — no clock read, no lock, no dict —
so the per-step instrumentation can stay in the trainer unconditionally.

Metric names are a scrape contract (pinned in
``tests/test_bench_contract.py``); bucket and phase label sets are
parsing contracts for the same reason.
"""

from __future__ import annotations

import collections
import gc
import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional

from dlti_tpu.telemetry import startup
from dlti_tpu.telemetry.registry import Counter, Gauge, ReadCounter
from dlti_tpu.telemetry.tracer import _NULL_SPAN
from dlti_tpu.utils.logging import get_logger

# ----------------------------------------------------------------------
# Bucket / phase catalogs (label contracts — postmortem, dashboards and
# the steplog parse these; pinned in tests/test_bench_contract.py)
# ----------------------------------------------------------------------

# Training wall-clock buckets a worker books itself. "step_compute" is
# the host-side dispatch of the compiled step; "device_sync" is the
# blocking wait for its results (where the device work actually
# surfaces); both count as PRODUCTIVE. "other" absorbs bookkeeping and
# anything not worth its own bucket — it must stay small, and because
# every second lands somewhere, a regression there is visible instead of
# invisible.
GOODPUT_BUCKETS = (
    "startup",            # init, compile, resume scan before first step
    "step_compute",       # compiled-step dispatch (host side)
    "device_sync",        # blocking wait on step results
    "data_wait",          # batch fetch stall (prefetch hides, not books)
    "host_to_device",     # global batch assembly / placement
    "eval",
    "checkpoint_save",
    "checkpoint_restore",  # verified resume at train start
    "rollback",           # sentinel rollback: restore + quarantine writes
    "replay",             # re-executing steps discarded by a rollback
    "sdc_probe",          # cross-rank param digest checks
    "shutdown",           # final saves / teardown
    "other",              # per-step bookkeeping, logging, residual host work
)

# Buckets only the elastic supervisor can book (stitched ledger).
SUPERVISOR_BUCKETS = ("restart_downtime",)

PRODUCTIVE_BUCKETS = ("step_compute", "device_sync")

# Serving per-request phases. A breakdown's values sum to the
# client-observed latency (enqueue-or-arrival → finish); "other" is the
# residual that keeps the sum exact when clamping eats sub-ms slivers.
REQUEST_PHASES = (
    "gateway_queue",   # admission-gateway queue (enqueue → engine submit)
    "queue",           # engine waiting deque (submit → slot admission)
    "tier_restore",    # host/disk prefix-block fetch + restore scatter
    "prefill",         # admission → first token, minus restore/stalls
    "failover",        # requeued after a replica fault, waiting again
    "preempt",         # preempted under memory pressure, waiting again
    "kv_handoff",      # disagg: prefill→decode paged-KV block migration
    "decode",          # first token → finish, minus requeue stalls
    "decode_prefill_stall",  # of decode: its engine was in others' prefills
    "other",           # residual (clamp slivers; sum stays exact)
)

# Name-stability contracts (pinned in tests/test_bench_contract.py).
LEDGER_METRIC_NAMES = (
    "dlti_goodput_fraction",
    "dlti_goodput_seconds_total",
    "dlti_goodput_mfu_percent",
)
REQUEST_PHASE_METRIC_NAMES = (
    "dlti_request_phase_seconds_total",
    "dlti_request_phase_requests_total",
)
STEPPER_METRIC_NAMES = (
    "dlti_stepper_phase_seconds_total",
    "dlti_stepper_phase_entries_total",
    "dlti_stepper_cpu_seconds_total",
    "dlti_stepper_device_wait_cpu_seconds_total",
    "dlti_stepper_marked_host_seconds_total",
    "dlti_stepper_marked_decode_steps_total",
    "dlti_stepper_stalls_total",
    "dlti_stepper_stall_seconds_total",
)
GC_METRIC_NAMES = (
    "dlti_gc_pause_seconds_total",
    "dlti_gc_collections_total",
)

# Module-level metrics (the checkpoint-store/watchdog pattern: trainer
# sets them, the server registry registers them for /metrics).
goodput_fraction_gauge = Gauge(
    LEDGER_METRIC_NAMES[0],
    help="fraction of booked wall clock spent in productive step compute")
goodput_seconds_total = Counter(
    LEDGER_METRIC_NAMES[1],
    help="wall-clock seconds booked per goodput bucket (bucket label)")
goodput_mfu_gauge = Gauge(
    LEDGER_METRIC_NAMES[2],
    help="model FLOPs utilization of the most recent training step")
phase_seconds_total = Counter(
    REQUEST_PHASE_METRIC_NAMES[0],
    help="per-request critical-path seconds per phase (phase label)")
phase_requests_total = Counter(
    REQUEST_PHASE_METRIC_NAMES[1],
    help="finished requests folded into the phase attribution")


# ----------------------------------------------------------------------
# Training: the phase clock
# ----------------------------------------------------------------------

class GoodputLedger:
    """Wall-clock phase clock with conservation by construction.

    Thread-safety: ``enter`` is called from the trainer's step thread
    only; ``totals``/``scalars`` may be read concurrently by the
    time-series sampler thread, so transitions and reads share one lock.
    """

    def __init__(self, enabled: bool = True,
                 clock: Callable[[], float] = time.monotonic):
        self.enabled = enabled
        self._clock = clock
        self._lock = threading.Lock()
        self._totals: Dict[str, float] = {}
        self._deltas: Dict[str, float] = {}
        self._phase = "startup"
        now = clock() if enabled else 0.0
        self._t0 = now
        self._start = now
        # While replaying rolled-back steps, step buckets reclass to
        # "replay": set to the pre-rollback high-water step by
        # begin_replay, cleared by end_replay.
        self.replay_until: Optional[int] = None

    # -- transitions ----------------------------------------------------
    def enter(self, phase: str) -> None:
        """Book time since the last transition to the previous phase and
        make ``phase`` current. Disabled: one attribute read."""
        if not self.enabled:
            return
        now = self._clock()
        with self._lock:
            prev = self._phase
            if self.replay_until is not None and prev in PRODUCTIVE_BUCKETS:
                prev = "replay"
            dt = max(0.0, now - self._t0)
            self._totals[prev] = self._totals.get(prev, 0.0) + dt
            self._deltas[prev] = self._deltas.get(prev, 0.0) + dt
            self._phase = phase
            self._t0 = now

    def begin_replay(self, until_step: int) -> None:
        """Steps (re-)executed while the optimizer step stays at or below
        ``until_step`` are rollback replay, not fresh progress."""
        if self.enabled:
            self.replay_until = int(until_step)

    def end_replay(self) -> None:
        self.replay_until = None

    # -- reads ----------------------------------------------------------
    def wall(self) -> float:
        """Seconds since construction (0.0 disabled)."""
        return self._clock() - self._start if self.enabled else 0.0

    def totals(self) -> Dict[str, float]:
        """Bucket seconds including the still-open current phase; the
        values sum to :meth:`wall` exactly (float rounding aside)."""
        if not self.enabled:
            return {}
        now = self._clock()
        with self._lock:
            out = dict(self._totals)
            cur = self._phase
            if self.replay_until is not None and cur in PRODUCTIVE_BUCKETS:
                cur = "replay"
            out[cur] = out.get(cur, 0.0) + max(0.0, now - self._t0)
        return out

    def take_deltas(self) -> Dict[str, float]:
        """Bucket seconds accrued since the previous call (the per-step
        feed for the steplog fields and the ``dlti_goodput_seconds_total``
        counter). Does not close the open phase — sub-transition time
        rides into the next call."""
        if not self.enabled:
            return {}
        with self._lock:
            d, self._deltas = self._deltas, {}
        return d

    def goodput_fraction(self,
                         totals: Optional[Dict[str, float]] = None) -> float:
        t = self.totals() if totals is None else totals
        wall = sum(t.values())
        if wall <= 0:
            return 0.0
        return sum(t.get(b, 0.0) for b in PRODUCTIVE_BUCKETS) / wall

    def scalars(self) -> Dict[str, float]:
        """``goodput_*`` keys for the time-series ring / ``/debug/vars``
        (what the watchdog's goodput_collapse rule and the flight-dump
        metrics snapshot consume)."""
        if not self.enabled:
            return {}
        t = self.totals()
        out = {f"goodput_{k}_seconds": round(v, 6) for k, v in t.items()}
        out["goodput_wall_seconds"] = round(sum(t.values()), 6)
        out["goodput_fraction"] = round(self.goodput_fraction(t), 6)
        return out

    def to_dict(self) -> dict:
        t = self.totals()
        return {"buckets": {k: round(v, 6) for k, v in t.items()},
                "wall_s": round(sum(t.values()), 6),
                "goodput_fraction": round(self.goodput_fraction(t), 6)}

    def save(self, path: str, **extra) -> Optional[str]:
        """Atomic JSON write of :meth:`to_dict` + ``extra``; never raises
        (accounting must not kill the run it accounts). None disabled."""
        if not self.enabled:
            return None
        try:
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            tmp = f"{path}.tmp-{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump({**self.to_dict(), **extra}, f)
            os.replace(tmp, path)
            return path
        except OSError:
            return None


# ----------------------------------------------------------------------
# Elastic stitching: one ledger across restarts
# ----------------------------------------------------------------------

def load_generation_ledgers(elastic_dir: str) -> List[dict]:
    """Parse every ``ledger_g*_r*.json`` a worker saved into the elastic
    rendezvous dir (``training.elastic.save_generation_ledger``)."""
    out: List[dict] = []
    try:
        names = sorted(os.listdir(elastic_dir))
    except OSError:
        return out
    for name in names:
        if not (name.startswith("ledger_g") and name.endswith(".json")):
            continue
        try:
            with open(os.path.join(elastic_dir, name)) as f:
                out.append(json.load(f))
        except (OSError, ValueError):
            continue
    return out


def stitch_ledgers(worker_ledgers: List[dict], timeline: List[dict],
                   num_slots: int) -> dict:
    """Stitch per-generation worker ledgers + the supervisor's generation
    timeline into one run-level ledger.

    ``timeline`` entries: ``{"generation", "world_size", "start", "end",
    "outcome"}`` on the supervisor's clock. Only the supervisor sees the
    two buckets workers cannot: ``restart_downtime`` (the gap between one
    generation's end and the next one's start — teardown residue, backoff,
    respawn) and shrunk-world degradation (wall clock run at
    ``world_size < num_slots``, with the pro-rata capacity loss).

    Worker buckets are taken from ONE rank per generation (rank 0 when
    present): ranks run the same step-synchronous schedule in parallel,
    so summing across ranks would double-count wall clock.
    """
    per_gen: Dict[int, List[dict]] = {}
    for w in worker_ledgers:
        per_gen.setdefault(int(w.get("generation", 0)), []).append(w)
    buckets: Dict[str, float] = {}
    generations = []
    for gen in sorted(per_gen):
        ws = sorted(per_gen[gen], key=lambda w: int(w.get("rank", 0)))
        rep = ws[0]
        for k, v in (rep.get("buckets") or {}).items():
            buckets[k] = buckets.get(k, 0.0) + float(v)
        generations.append({
            "generation": gen, "rank": rep.get("rank"),
            "wall_s": rep.get("wall_s"),
            "goodput_fraction": rep.get("goodput_fraction"),
            "buckets": rep.get("buckets") or {},
            "num_rank_ledgers": len(ws),
        })
    segs = sorted(timeline, key=lambda s: s.get("start", 0.0))
    downtime = sum(max(0.0, b["start"] - a["end"])
                   for a, b in zip(segs, segs[1:]))
    shrunk_wall = 0.0
    shrunk_loss = 0.0
    for s in segs:
        wall = max(0.0, float(s.get("end", 0.0)) - float(s.get("start", 0.0)))
        world = int(s.get("world_size", num_slots))
        if 0 < world < num_slots:
            shrunk_wall += wall
            shrunk_loss += wall * (num_slots - world) / num_slots
    if downtime > 0:
        buckets["restart_downtime"] = round(
            buckets.get("restart_downtime", 0.0) + downtime, 6)
    total = sum(buckets.values())
    productive = sum(buckets.get(b, 0.0) for b in PRODUCTIVE_BUCKETS)
    return {
        "num_slots": num_slots,
        "num_generations": len(segs) or len(generations),
        "generations": generations,
        "buckets": {k: round(v, 6) for k, v in buckets.items()},
        "wall_s": round(total, 6),
        "restart_downtime_s": round(downtime, 6),
        "shrunk_world_s": round(shrunk_wall, 6),
        "shrunk_world_capacity_loss_s": round(shrunk_loss, 6),
        "goodput_fraction": round(productive / total, 6) if total else 0.0,
    }


# ----------------------------------------------------------------------
# Serving: the stepper thread's phase clock
# ----------------------------------------------------------------------

# What a phase is to the thread, declared where it is entered
# (``account.phase(name, cat, kind)``) and carried as the ``kind`` label of
# its series, so that neither this module nor a reader keeps a list of
# names. HOST: work on the path of a decode round, not meant to stand still.
# WAIT: where the thread waits by design (for work). DEVICE_WAIT: where it
# waits for the device; the thread's CPU in there is booked apart.
HOST, WAIT, DEVICE_WAIT = "host", "wait", "device_wait"
# The phase the clock starts in: what of the owner's time is inside no phase.
# Under a server that is its loop's own tests and jumps (microseconds); for
# an engine driven without one it is the caller's time between two steps.
# Nothing of a round either way, so a wait.
STEPPER_BASE_PHASE = "server/loop"
# The thread's CPU clock is a system call, and a slow one where the host is
# a virtual machine (5.5 us a read on the benchmark's v5e host against
# 0.09 us for the wall clock: my chip run, PR 43): read every round, four
# reads would cost more than the 24 transitions. It is also a coarse one
# there (it ticks in steps of 10 ms: my chip run, PR 44), so an interval of
# one step read from it and scaled is mostly the tick's noise. So one step
# in this many is a marked step: at its entry the thread's running total is
# read (exact to a tick however long the run), and round its device waits,
# which no running total holds, the clock is read and what it shows is
# booked times this many.
STEPPER_CPU_MARK_EVERY = 16
# A host phase that went this long without a transition is a stall (host
# phases last under 15 ms at 32 live slots).
STEPPER_STALL_S = 0.25


_thread_id = threading.get_ident


def _programs_built() -> float:
    """Programs this process has compiled or fetched from the persistent
    cache so far (``telemetry.startup``'s listener counts them)."""
    return (startup.compilations_total.value
            + startup.compile_cache_hits_total.value)


class _Phase:
    """One phase of a :class:`StepperAccount` and its books; as a context
    manager, the transition into it and back. Holds no state of an entry
    (that is on the account's stacks), so one object serves every entry."""

    __slots__ = ("_acct", "name", "cat", "kind", "host", "_waits_for_device",
                 "_begins_step", "seconds", "entries", "stalls",
                 "stall_seconds")

    def __init__(self, acct: "StepperAccount", name: str, cat: str,
                 kind: str, step: bool):
        self._acct = acct
        self.name = name
        self.cat = cat
        self.kind = kind
        self.host = kind == HOST
        self._waits_for_device = kind == DEVICE_WAIT
        self._begins_step = step
        self.seconds = 0.0
        self.entries = 0
        self.stalls = 0
        self.stall_seconds = 0.0

    def __enter__(self):
        a = self._acct
        stack = a._stack
        if a._tracer.enabled:
            # The tracer's span of this entry, kept with the depth it was
            # opened at: a phase entered while the tracer was off has none.
            # The step's span alone keeps its thread's CPU (``cpu_us``).
            span = a._tracer.span(self.name, self.cat, self._begins_step)
            span.__enter__()
            a._spans.append((len(stack), span))
        now = a._clock()
        dt = now - a.last
        cur = stack[-1]
        cur.seconds += dt
        if dt > STEPPER_STALL_S and cur.host:
            a._stood_still(cur, dt, now)
        a.last = now
        stack.append(self)
        self.entries += 1
        if self._begins_step:
            a._step_began()
        elif self._waits_for_device and a._marked_step:
            a._wait_cpu0 = a._cpu_clock()

    def __exit__(self, exc_type, exc, tb):
        a = self._acct
        now = a._clock()
        dt = now - a.last
        self.seconds += dt
        if dt > STEPPER_STALL_S and self.host:
            a._stood_still(self, dt, now)
        a.last = now
        a._stack.pop()
        if a._marked_step:
            if self._waits_for_device:
                a.device_wait_cpu_seconds += STEPPER_CPU_MARK_EVERY * (
                    a._cpu_clock() - a._wait_cpu0)
            elif self._begins_step:
                a._marked_step = False
        if a._spans and a._spans[-1][0] == len(a._stack):
            a._spans.pop()[1].__exit__(exc_type, exc, tb)


class StepperAccount:
    """The phase clock of the thread that steps a serving engine.

    One thread owns it (the first to enter a phase, or the one that called
    :meth:`bind`). ``with account.phase(name, cat, kind):`` is the one way
    into a phase: it books the transition always, and opens the tracer's
    ring span and profiler annotation of the same name only while the tracer
    is enabled, so the phases an operator scrapes and the spans a capture
    holds are the same intervals under the same names. Another thread that
    comes through the same code (a disaggregated fleet's prefill thread
    shares its engines' telemetry) gets the tracer's span alone.

    Conservation by construction: the clock starts in
    :data:`STEPPER_BASE_PHASE`, every transition books the time since the
    last one to the innermost open phase, so the phases' seconds sum to
    ``last - start``, the owner's wall time up to its last transition.
    The time of the phase still open is booked when it ends; a scrape reads
    plain floats and never sees a half-made transition count twice.

    The thread's CPU clock is read in a *marked* step alone: one entry in
    :data:`STEPPER_CPU_MARK_EVERY` of the phase that begins a step
    (``phase(..., step=True)``). At its entry three running totals are
    taken as of one instant, so that a ratio of their changes is of like
    with like: ``cpu_seconds`` (the thread's CPU since it was bound),
    ``marked_host_seconds`` (the wall of its host phases) and
    ``marked_decode_steps`` (by :attr:`steps_done`). Round that step's
    :data:`DEVICE_WAIT` phases the clock is read too, and what they took is
    booked times ``STEPPER_CPU_MARK_EVERY`` (``device_wait_cpu_seconds``:
    an estimate from one step in sixteen; no running total holds it). Host
    wall less the CPU outside the device waits is time the thread was
    runnable and did not run: the interpreter lock, or the OS.
    """

    def __init__(self, tracer, clock: Callable[[], float] = time.monotonic,
                 cpu_clock: Callable[[], float] = time.thread_time):
        self._tracer = tracer
        self._clock = clock
        self._cpu_clock = cpu_clock
        self._logger = get_logger()
        self._phases: Dict[str, _Phase] = {}
        self._owner: Optional[int] = None
        self._stack: List[_Phase] = [self._new_phase(STEPPER_BASE_PHASE,
                                                     "server", WAIT, False)]
        self._spans: list = []
        self.start = self.last = clock()
        self.cpu_seconds = 0.0
        self.device_wait_cpu_seconds = 0.0
        self.marked_host_seconds = 0.0
        self.marked_decode_steps = 0.0
        self._cpu_base = 0.0
        self._wait_cpu0 = 0.0
        self._steps_to_mark = 1
        self._marked_step = False
        # As of the last marked step's entry (or the bind): what a stall's
        # record is counted from.
        self._mark_wall = self.start
        self._mark_cpu = 0.0
        self._mark_process = 0.0
        self._mark_gc = gc_totals()
        # As of the last mark or the last phase found over the limit: a
        # phase that built a program since then was not standing still.
        self._programs_seen = 0.0
        # Of the engine stepped (the server's stepper sets them):
        # ``() -> {"live_slots": n, "waiting": n}`` for a stall's record,
        # and ``() -> decode steps so far`` for the marked steps' count.
        self.describe: Optional[Callable[[], dict]] = None
        self.steps_done: Optional[Callable[[], int]] = None
        # ``() -> str``: what the engine stepped was last asked to run (the
        # shape of its newest prefill call), for the line that names a
        # program built after start-up (the engine sets it).
        self.doing: Optional[Callable[[], str]] = None

    def _new_phase(self, name: str, cat: str, kind: str,
                   step: bool) -> _Phase:
        p = self._phases[name] = _Phase(self, name, cat, kind, step)
        return p

    def bind(self) -> None:
        """Make the calling thread the owner, in the base phase, from now:
        what another thread left open is dropped with its thread, and the
        tracer's ``profiler/start`` instant and a late program's log line
        learn the open phase here."""
        self._owner = _thread_id()
        del self._stack[1:]
        del self._spans[:]
        self.last = self._clock()
        self.start = self.last - sum(
            p.seconds for p in self._phases.values())
        self._marked_step = False
        self._tracer.capture_context = self.open_phase
        startup.stepper_phase = self._where
        # A first call of a shape builds its program inside a host phase:
        # count the programs built, to tell that from a stall.
        startup.install_compile_listener()
        cpu = self._cpu_clock()
        self._cpu_base = cpu - self.cpu_seconds
        self._mark(cpu)

    def phase(self, name: str, cat: str = "server", kind: str = HOST,
              step: bool = False):
        """The context manager of one phase, for the owner; for any other
        thread the tracer's span of that name and nothing booked. ``kind``
        and ``step`` (entering it begins a step: the CPU mark) are a phase's
        for good, as declared where it is first entered."""
        if self._owner != _thread_id():
            if self._owner is not None:
                return self._tracer.span(name, cat)
            self.bind()
        try:
            return self._phases[name]
        except KeyError:
            return self._new_phase(name, cat, kind, step)

    def _doing_note(self) -> str:
        doing = self.doing() if self.doing is not None else ""
        return f" ({doing})" if doing else ""

    def _where(self) -> str:
        """The open phase by name and, where the engine says it, what it
        was last asked to run."""
        return self._stack[-1].name + self._doing_note()

    def mine(self) -> bool:
        """Whether the calling thread owns the account: ``last`` is then
        its own last transition (for what is timed off the phases' reads)."""
        return self._owner == _thread_id()

    def open_phase(self) -> dict:
        """The innermost open phase and when its current stretch began
        (microseconds on the tracer's clock). Read from any thread: a read
        that falls into a transition may pair one's name with the other's
        start."""
        return {"stepper_phase": self._stack[-1].name,
                "stepper_phase_since_us": self.last * 1e6}

    # -- the step's marks, and a stall's record -------------------------
    def _step_began(self) -> None:
        self._steps_to_mark -= 1
        if self._steps_to_mark:
            return
        self._steps_to_mark = STEPPER_CPU_MARK_EVERY
        self._marked_step = True
        if _GC_BOOK.parked:
            _GC_BOOK.flush()
        # The three totals as of this instant (the clock last: nearest to
        # the step's work).
        self.marked_host_seconds = sum(
            p.seconds for p in self._phases.values() if p.host)
        if self.steps_done is not None:
            self.marked_decode_steps = self.steps_done()
        cpu = self._cpu_clock()
        self.cpu_seconds = cpu - self._cpu_base
        self._mark(cpu)

    def _mark(self, cpu: float) -> None:
        self._mark_wall = self.last
        self._mark_cpu = cpu
        self._mark_process = time.process_time()
        self._mark_gc = gc_totals()
        self._programs_seen = _programs_built()

    def _stood_still(self, phase: _Phase, dt: float, now: float) -> None:
        """A host phase went ``dt`` (over the limit) without a transition:
        a stall and its record, unless it built a program meanwhile (a
        first call of a shape compiles inside ``engine/prefill_launch`` or
        ``engine/decode_launch``: one INFO line with the programs' names,
        nothing booked)."""
        built = _programs_built()
        if built != self._programs_seen:
            n = int(built - self._programs_seen)
            self._logger.info(
                "stepper: %.3f s in %s%s, %d program(s) compiled or fetched "
                "meanwhile: %s", dt, phase.name, self._doing_note(), n,
                ", ".join(f"{name} ({how}, {s:.3f} s)" for name, s, how
                          in startup.recent_programs(n)))
            self._programs_seen = built
            return
        phase.stalls += 1
        phase.stall_seconds += dt
        pause0, count0 = self._mark_gc
        pause, count = gc_totals()
        about = self.describe() if self.describe is not None else {}
        self._tracer.instant("server/stall", cat="server", phase=phase.name,
                             seconds=round(dt, 6))
        # (The CPU clocks are read at one step's entry in
        # STEPPER_CPU_MARK_EVERY, so the deltas are over the stretch the
        # line names: the stall and at most that many steps before it.)
        self._logger.warning(
            "stepper stalled: %.3f s in %s without a transition; in the "
            "%.3f s since the last CPU mark: stepper cpu %.3f s, process "
            "cpu %.3f s, gc pause %.3f s in %s collections (generations 0, "
            "1, 2); live slots %s, waiting %s",
            dt, phase.name, now - self._mark_wall,
            self._cpu_clock() - self._mark_cpu,
            time.process_time() - self._mark_process,
            sum(pause) - sum(pause0),
            [b - a for a, b in zip(count0, count)],
            about.get("live_slots", "?"), about.get("waiting", "?"))

    # -- reads ----------------------------------------------------------
    def wall(self) -> float:
        """The owner's wall time from the start to its last transition:
        what the phases' seconds sum to."""
        return self.last - self.start

    def seconds(self) -> Dict[str, float]:
        return {p.name: p.seconds for p in list(self._phases.values())}

    def entries(self) -> Dict[str, int]:
        return {p.name: p.entries for p in list(self._phases.values())
                if p.entries}

    def stalls(self) -> Dict[str, int]:
        """By host phase entered so far (0 in a quiet run: the family is
        on ``/metrics`` before the first stall)."""
        return {p.name: p.stalls for p in list(self._phases.values())
                if p.host and p.entries}

    def stall_seconds(self) -> float:
        return sum(p.stall_seconds for p in list(self._phases.values()))

    def metrics(self) -> tuple:
        """The series of :data:`STEPPER_METRIC_NAMES`, read from the books
        when scraped (``telemetry.registry.ReadCounter``)."""
        n = STEPPER_METRIC_NAMES
        mark = f"as of the last marked step's entry (one step in " \
               f"{STEPPER_CPU_MARK_EVERY})"

        def phases():
            return list(self._phases.values())

        return (
            ReadCounter(n[0], lambda: {(p.name, p.kind): p.seconds
                                       for p in phases()},
                        label=("phase", "kind"),
                        help="wall seconds of the stepper thread by its "
                             "innermost open phase; the phases sum to the "
                             "thread's wall time (kind: host work, a wait "
                             "for work, a wait for the device)"),
            ReadCounter(n[1], lambda: {(p.name, p.kind): p.entries
                                       for p in phases() if p.entries},
                        label=("phase", "kind"),
                        help="entries into each phase of the stepper"),
            ReadCounter(n[2], lambda: {"": self.cpu_seconds},
                        help=f"CPU seconds of the stepper thread, {mark}"),
            ReadCounter(n[3], lambda: {"": self.device_wait_cpu_seconds},
                        help="of them, inside the waits for the device: "
                             "those of the marked steps, times "
                             f"{STEPPER_CPU_MARK_EVERY}"),
            ReadCounter(n[4], lambda: {"": self.marked_host_seconds},
                        help="wall seconds of the stepper's host phases, "
                             f"{mark}"),
            ReadCounter(n[5], lambda: {"": self.marked_decode_steps},
                        help=f"decode steps of the engine stepped, {mark}"),
            ReadCounter(n[6], self.stalls, label="phase",
                        help=f"host phases that went {STEPPER_STALL_S} s "
                             f"without a transition (and built no program "
                             f"meanwhile)"),
            ReadCounter(n[7], lambda: {"": self.stall_seconds()},
                        help="wall seconds of those stalls"),
        )


class NullStepperAccount:
    """An account that books nothing and opens no span: the engine's step
    without the clock, for the test that the clock changes no output and
    for the measurement of what it costs."""

    last = 0.0
    describe = steps_done = doing = None

    def phase(self, name: str, cat: str = "server", kind: str = HOST,
              step: bool = False):
        return _NULL_SPAN

    def bind(self) -> None:
        pass

    def mine(self) -> bool:
        return False

    def metrics(self) -> tuple:
        return ()


# ----------------------------------------------------------------------
# The collector's pauses
# ----------------------------------------------------------------------

class _GcBook:
    """Pause seconds and collections by generation, written by the
    ``gc.callbacks`` hook on whichever thread ran the collection (one
    collection runs at a time) and read as plain lists.

    The hook takes no lock and calls nothing that does: a collection starts
    at any bytecode boundary of any thread, also inside the tracer's
    ``_append`` with the ring's lock held, and a hook that appended its span
    there would wait for its own thread (it did, in one ring-on run in nine:
    my chip runs, PR 43). So a collection's span is parked here and goes to
    the ring from :meth:`flush`, which the stepper's account calls at a
    marked step and :func:`remove_gc_hook` at the end."""

    def __init__(self):
        self.pause = [0.0, 0.0, 0.0]
        self.count = [0, 0, 0]
        self.t0 = 0.0
        self.tracer = None
        self.installed = False   # ever: the series then stand at 0 or more
        self.parked: collections.deque = collections.deque(maxlen=1024)

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.t0 = time.monotonic()
            return
        now = time.monotonic()
        gen = info["generation"]
        self.pause[gen] += now - self.t0
        self.count[gen] += 1
        if self.tracer is not None and self.tracer.enabled:
            self.parked.append((self.t0, now, _thread_id(), gen,
                                info.get("collected", 0)))

    def flush(self) -> None:
        """The parked collections into the tracer's ring, as ``gc/collect``
        spans on the threads that ran them. Called where no ring lock is
        held."""
        tracer = self.tracer
        while self.parked and tracer is not None:
            t0, t1, tid, gen, collected = self.parked.popleft()
            tracer.complete("gc/collect", t0, t1, cat="gc", tid=tid,
                            generation=gen, collected=collected)


_GC_BOOK = _GcBook()

gc_pause_seconds_total = ReadCounter(
    GC_METRIC_NAMES[0],
    lambda: dict(enumerate(_GC_BOOK.pause)) if _GC_BOOK.installed else {},
    label="generation",
    help="wall seconds inside the cyclic collector, by generation "
         "(booked while install_gc_hook's hook is in)")
gc_collections_total = ReadCounter(
    GC_METRIC_NAMES[1],
    lambda: dict(enumerate(_GC_BOOK.count)) if _GC_BOOK.installed else {},
    label="generation", help="collections of the cyclic collector")


def gc_totals() -> tuple:
    """``(pause seconds, collections)`` by generation so far, as tuples."""
    return tuple(_GC_BOOK.pause), tuple(_GC_BOOK.count)


def install_gc_hook(tracer=None) -> None:
    """Book every collection from now on (idempotent); with ``tracer``, a
    ``gc/collect`` ring span for each while that tracer is enabled (put
    into the ring a little later: :class:`_GcBook`). An entry point installs
    it at start-up and removes it at shutdown."""
    _GC_BOOK.tracer = tracer
    _GC_BOOK.installed = True
    if _GC_BOOK not in gc.callbacks:
        gc.callbacks.append(_GC_BOOK)


def remove_gc_hook() -> None:
    if _GC_BOOK in gc.callbacks:
        gc.callbacks.remove(_GC_BOOK)
    _GC_BOOK.flush()
    _GC_BOOK.tracer = None


# ----------------------------------------------------------------------
# Serving: per-request critical-path attribution
# ----------------------------------------------------------------------

def note_requeue(req, kind: str) -> None:
    """Mark a request leaving a slot back to a waiting queue (``kind`` in
    ``("failover", "preempt", "kv_handoff")``); the wait until
    re-admission books to that phase instead of inflating prefill/decode.

    A mark may already be open: a slot preempted mid-chunked-prefill whose
    replica then dies is requeued AGAIN (failover) before the preempt wait
    was ever closed by a re-admission. Fold the open window into its phase
    first — overwriting the mark would silently drop the elapsed wait and
    restart the charge window, and the lost time would book into prefill.
    """
    note_readmitted(req)
    req._requeue_mark = (kind, time.monotonic())


def note_readmitted(req) -> None:
    """Close an open requeue mark at (re-)admission time."""
    mark = getattr(req, "_requeue_mark", None)
    if not mark:
        return
    kind, t0 = mark
    req._requeue_mark = None
    dt = max(0.0, time.monotonic() - t0)
    req.stall_s[kind] = req.stall_s.get(kind, 0.0) + dt
    if req.first_token_time is None:
        req.stall_prefill_s += dt


def request_breakdown(req, end: Optional[float] = None) -> dict:
    """Assemble a request's recorded timestamps into a phase breakdown
    whose values sum to the client-observed latency (t0 = gateway enqueue
    when the request came through one, else engine arrival; end = finish).

    Returns ``{"total_s", "ttft_s", "phases": {phase: s}, "timeline":
    [(event, offset_s)]}``; ``phases`` keys come from
    :data:`REQUEST_PHASES` and always include the ``other`` residual that
    keeps the sum exact when clamping trims negative slivers.
    """
    gw_t = getattr(req, "gateway_enqueue_time", None)
    t0 = gw_t if gw_t is not None else req.arrival_time
    end = req.finish_time if req.finish_time is not None \
        else (end if end is not None else time.monotonic())
    first = req.first_token_time
    admitted = req.admitted_time
    restore = float(getattr(req, "restore_s", 0.0))
    stall = dict(getattr(req, "stall_s", {}) or {})
    stall_pre = float(getattr(req, "stall_prefill_s", 0.0))
    mark = getattr(req, "_requeue_mark", None)
    if mark:  # died waiting on a requeue (e.g. failover exhausted)
        dt = max(0.0, end - mark[1])
        stall[mark[0]] = stall.get(mark[0], 0.0) + dt
        if first is None:
            stall_pre += dt
    stall_total = sum(stall.values())
    stall_pre = min(stall_pre, stall_total)

    phases: Dict[str, float] = {}
    timeline: List[tuple] = [("submitted", max(0.0, req.arrival_time - t0))]
    if gw_t is not None:
        phases["gateway_queue"] = max(0.0, req.arrival_time - gw_t)
        timeline.insert(0, ("gateway_enqueue", 0.0))
    adm = admitted if admitted is not None else (first or end)
    phases["queue"] = max(0.0, adm - req.arrival_time)
    if admitted is not None:
        timeline.append(("admitted", max(0.0, admitted - t0)))
    if restore > 0:
        phases["tier_restore"] = restore
    pre_end = first if first is not None else end
    phases["prefill"] = max(0.0, (pre_end - adm) - restore - stall_pre)
    if first is not None:
        timeline.append(("first_token", max(0.0, first - t0)))
        phases["decode"] = max(0.0, (end - first)
                               - (stall_total - stall_pre))
        # Of its decode, the wall its engines spent in prefill calls of
        # other requests while it held a decoding slot (the engine settles
        # the sum when the request leaves a slot): moved out of "decode",
        # so the two sum to what "decode" was.
        held = min(float(getattr(req, "prefill_stall_s", 0.0)),
                   phases["decode"])
        if held > 0:
            phases["decode"] -= held
            phases["decode_prefill_stall"] = held
    for kind, s in stall.items():
        if s > 0:
            phases[kind] = s
    timeline.append(("finish", max(0.0, end - t0)))
    total = round(max(0.0, end - t0), 6)
    # The residual is computed AGAINST THE ROUNDED values: the emitted
    # phases sum to the emitted total exactly (per-phase rounding would
    # otherwise leak a few microseconds of drift into consumers'
    # conservation checks).
    rounded = {k: round(v, 6) for k, v in phases.items()}
    residual = round(total - sum(rounded.values()), 6)
    rounded["other"] = max(0.0, residual)
    if residual < 0:
        # Per-phase round-ups can overshoot the rounded total by a few
        # microseconds; shave the excess off the largest phase so the
        # emitted numbers conserve exactly.
        top = max(rounded, key=lambda k: rounded[k])
        rounded[top] = round(rounded[top] + residual, 6)
    return {
        "total_s": total,
        "ttft_s": (round(first - t0, 6) if first is not None else None),
        "phases": rounded,
        "timeline": [(name, round(off, 6)) for name, off in timeline],
    }


class SlowLog:
    """Bounded retention of the K worst (slowest) finished requests with
    their full phase timelines — the ``GET /debug/slow`` payload."""

    def __init__(self, k: int = 32):
        self.k = max(1, int(k))
        self._lock = threading.Lock()
        self._entries: List[dict] = []

    def add(self, entry: dict) -> None:
        with self._lock:
            self._entries.append(entry)
            self._entries.sort(key=lambda e: -e.get("total_s", 0.0))
            del self._entries[self.k:]

    def worst(self, n: Optional[int] = None) -> List[dict]:
        with self._lock:
            out = list(self._entries)
        return out if n is None else out[:n]

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


class CriticalPathTracker:
    """Folds finished requests into the phase exposition + slow log.
    One per :class:`RequestTelemetry` (shared across replicas). Per
    REQUEST, not per token — and ``enabled = False`` reduces
    ``observe()`` to one attribute read."""

    def __init__(self, slow_k: int = 32):
        self.enabled = True
        self.slow = SlowLog(slow_k)

    def observe(self, req) -> Optional[dict]:
        if not self.enabled:
            return None
        if getattr(req, "_cp_observed", False):
            return None  # failover-errored requests can finish twice
        req._cp_observed = True
        b = request_breakdown(req)
        phase_requests_total.inc()
        for k, v in b["phases"].items():
            if v > 0:
                phase_seconds_total.labels(phase=k).inc(v)
        self.slow.add({
            "id": req.request_id,
            "trace_id": getattr(req, "trace_id", ""),
            "tenant": req.tenant,
            "priority": req.priority,
            "replica": req.replica,
            "finish_reason": req.finish_reason,
            "prompt_tokens": len(req.prompt_token_ids),
            "output_tokens": len(req.output_token_ids),
            "preemptions": req.num_preemptions,
            "retries": req.num_retries,
            "wall": time.time(),
            "total_s": b["total_s"],
            "ttft_s": b["ttft_s"],
            "phases": b["phases"],
            "timeline": b["timeline"],
        })
        return b
