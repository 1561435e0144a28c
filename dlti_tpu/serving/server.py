"""OpenAI-compatible HTTP server over the continuous-batching engine.

Closes the reference's claimed-but-absent serving leg: "High-throughput
serving with vLLM and tensor parallelism" (``README.md:10``), "REST API"
(``README.md:16``) — no code in the reference repo (SURVEY.md §0). Endpoints
mirror the vLLM/OpenAI surface the reference's pins imply:

* ``POST /v1/completions``        — text completion, optional SSE streaming
* ``POST /v1/chat/completions``   — chat with the Llama-2 template the
  reference's data pipeline defines (``scripts/prepare_dataset.py:12-25``:
  ``<s>[INST] {q} [/INST] {a}</s>``)
* ``GET /v1/models`` · ``GET /health`` · ``GET /stats`` ·
  ``GET /metrics`` (Prometheus text exposition of the same counters)

Stdlib only (``http.server`` + threads): the engine steps in one background
thread (the TPU is a single serialized stream anyway); handler threads block
on per-request token queues. No aiohttp/FastAPI dependency.
"""

from __future__ import annotations

import json
import os
import queue
import threading
import time
import uuid
import dataclasses
from dataclasses import dataclass, field
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple

from dlti_tpu.config import GatewayConfig, TelemetryConfig
from dlti_tpu.data.tokenizer import Tokenizer
from dlti_tpu.serving.engine import InferenceEngine, Request
from dlti_tpu.serving.gateway import (
    AdmissionError, AdmissionGateway, PRIORITIES, affinity_key_from,
    tenant_from_headers,
)
from dlti_tpu.serving.sampling import SamplingParams
from dlti_tpu.telemetry import (
    AnomalyWatchdog, FlightRecorder, MetricsRegistry, TimeSeriesSampler,
    get_recorder, get_tracer, install_recorder, render_dashboard_html,
    request_breakdown,
)
from dlti_tpu.telemetry.ledger import (
    REQUEST_PHASES as _REQUEST_PHASES, WAIT, gc_collections_total,
    gc_pause_seconds_total,
)
from dlti_tpu.telemetry.registry import Counter
from dlti_tpu.utils.logging import get_logger

# What the streaming handlers pay a token (detokenise the answer so far,
# scan for stop strings, write the frame), on threads that share the
# interpreter lock with the stepper: CPU seconds over events is the cost of
# one event where it is paid.
sse_handler_cpu_seconds_total = Counter(
    "dlti_sse_handler_cpu_seconds_total",
    help="thread CPU seconds of the streaming handlers, booked at every "
         "64th event of a thread")
sse_events_total = Counter(
    "dlti_sse_events_total",
    help="events of the streaming handlers that CPU is booked for (whole "
         "blocks of 64 a thread)")
for _c in (sse_handler_cpu_seconds_total, sse_events_total):
    _c.inc(0)  # the series exists from the start
_handler = threading.local()


class _HandlerMeter:
    """A handler thread's CPU over the events it streams. The thread's CPU
    clock is a slow system call on a virtual host
    (``telemetry.ledger.STEPPER_CPU_MARK_EVERY``), read under the
    interpreter lock the stepper needs, and there it ticks in steps of
    10 ms (my chip run, PR 44): so it is read at one event in ``EVERY``
    and never scaled. What is booked then is the thread's running total
    since its last read (since the thread began, the first time: a handler
    thread does nothing but handle), for the ``EVERY`` events since: the
    clock's error stays one tick a thread however many blocks it books, and
    a response's last events, short of a block, are left out of both
    series alike. One meter a thread, kept across its responses; an event
    otherwise costs one add."""

    EVERY = 64
    __slots__ = ("events", "_cpu")

    def __init__(self):
        self.events = 0
        self._cpu = 0.0

    @classmethod
    def of_this_thread(cls) -> "_HandlerMeter":
        try:
            return _handler.meter
        except AttributeError:
            meter = _handler.meter = cls()
            return meter

    def event(self) -> None:
        """An event has arrived."""
        self.events += 1
        if not self.events % self.EVERY:
            now = time.thread_time()
            sse_handler_cpu_seconds_total.inc(now - self._cpu)
            sse_events_total.inc(self.EVERY)
            self._cpu = now


# /stats keys exposed as Prometheus gauges (point-in-time values); every
# other numeric stat is a monotonic counter. Name-stability contract: the
# exposition names are dlti_<key> — scraped by external dashboards, so keys
# here and in the engine's stats dict must not be renamed.
_GAUGE_KEYS = ("active_seqs", "waiting", "free_blocks",
               "recurrent_state_pool_bytes", "prefill_widest_call_tokens")


def build_registry(async_engine: "AsyncEngine") -> MetricsRegistry:
    """The single backing store for ``/stats`` and ``/metrics``: engine
    counters ride in as a scalar-source callback (the engine's ``stats``
    dict stays the source of truth — no registry lock on the decode path),
    and the engine's request-lifecycle histograms (TTFT / TPOT / queue
    time) register for exposition."""
    registry = MetricsRegistry()

    def _engine_scalars() -> dict:
        eng = async_engine.engine
        return {
            **eng.stats,
            "active_seqs": eng.num_active,
            "waiting": len(eng.waiting),
            "free_blocks": eng.num_free_blocks,
            "recurrent_state_pool_bytes":
                getattr(eng, "recurrent_state_pool_bytes", 0),
        }

    registry.add_scalar_source(_engine_scalars, gauge_keys=_GAUGE_KEYS,
                               prefix="dlti_")
    for hist in async_engine.engine.telemetry.histograms():
        registry.register(hist)
    # The cache's books by group of layers (blocks in use, released, the
    # live context): read from the engine at a scrape. (A facade over
    # several engines has none.)
    for metric in getattr(async_engine.engine, "kv_metrics", tuple)():
        registry.register(metric)
    # The stepper's phase clock, the collector's pauses and the streaming
    # handlers' CPU: always on, read from their writers' books at a scrape.
    for metric in (*async_engine.engine.telemetry.stepper.metrics(),
                   gc_pause_seconds_total, gc_collections_total,
                   sse_handler_cpu_seconds_total, sse_events_total):
        registry.register(metric)
    # Self-monitoring series: the span ring's eviction counter (truncated
    # forensics must be self-announcing) plus the module-level watchdog /
    # flight-recorder counters (shared with any trainer in-process).
    registry.add_scalar_source(
        lambda: {"trace_dropped_events": get_tracer().dropped_events},
        prefix="dlti_")
    from dlti_tpu.telemetry.flightrecorder import dumps_total
    from dlti_tpu.telemetry.watchdog import alerts_total

    registry.register(alerts_total)
    registry.register(dumps_total)
    # Start-up phases and the compile / cache-fetch counters
    # (telemetry.startup): written by the entry point and by JAX's
    # compile listener, never by the step loop.
    from dlti_tpu.telemetry.startup import STARTUP_METRICS

    for metric in STARTUP_METRICS:
        registry.register(metric)
    # Distributed-tracing federation counters (module-level, like the
    # watchdog/flight pair): spans adopted from fleet workers, spans
    # that arrived without any request/trace parentage, and the per-
    # worker clock-offset estimate the rebasing used. Registered even on
    # single-process engines so the series exists (at zero) and the
    # metric-naming contract can walk it.
    from dlti_tpu.telemetry import distributed_trace as _dtrace

    registry.register(_dtrace.federated_spans_total)
    registry.register(_dtrace.unparented_spans_total)
    registry.register(_dtrace.clock_offset_gauge)
    # Numeric-fault sentinel + SDC counters (dlti_tpu.training.sentinel):
    # module-level like the watchdog/flight pair, so an in-process
    # trainer's anomalies and the serving guard drills share one series
    # and /dashboard plots them.
    from dlti_tpu.training import sentinel as _sentinel

    for metric in (_sentinel.anomalies_total,
                   _sentinel.skipped_updates_total,
                   _sentinel.rollbacks_total,
                   _sentinel.quarantined_windows_total,
                   _sentinel.sdc_probes_total,
                   _sentinel.sdc_mismatches_total):
        registry.register(metric)
    # Continuous-delivery counters (serving.deploy): module-level like the
    # watchdog/flight pair, so the sampler rings them for /dashboard and
    # the watchdog's canary_regression rule watches rollbacks grow.
    from dlti_tpu.serving import deploy as _deploy

    for metric in (_deploy.candidates_total, _deploy.canaries_total,
                   _deploy.promotions_total, _deploy.rollbacks_total,
                   _deploy.rejected_total, _deploy.incumbent_step_gauge):
        registry.register(metric)
    # Tiered prefix-cache telemetry (module-level like the watchdog /
    # flight counters, so replicas aggregate into one series): per-tier
    # hit/miss/eviction/promotion/demotion counters + block gauges.
    from dlti_tpu.serving import prefix_cache as _pc

    for metric in (_pc.hits_total, _pc.misses_total, _pc.evictions_total,
                   _pc.promotions_total, _pc.demotions_total,
                   _pc.blocks_gauge):
        registry.register(metric)
    # Multi-LoRA adapter pool telemetry (serving.adapters): module-level
    # like the prefix-cache counters — pool load/evict/hit/miss counters
    # plus the slot/byte gauges, one series across replicas.
    from dlti_tpu.serving import adapters as _ad

    for metric in (_ad.loads_total, _ad.evictions_total,
                   _ad.pool_hits_total, _ad.pool_misses_total,
                   _ad.pool_slots_gauge, _ad.pool_bytes_gauge):
        registry.register(metric)

    def _prefix_hit_rate() -> dict:
        # Derived hit-rate gauge so /dashboard gets a ready-made series
        # (the raw token counters are cumulative; a sparkline of the
        # ratio is what a human actually reads during a run): fraction of
        # prompt tokens served from cache — HBM hits plus lower-tier
        # restores — over everything the engine handled.
        s = async_engine.engine.stats
        cached = s.get("prefix_cached_tokens", 0)
        restored = s.get("prefix_restored_tokens", 0)
        total = cached + restored + s.get("prefill_tokens", 0)
        return {"prefix_cache_hit_rate":
                (cached + restored) / total if total else 0.0}

    registry.add_scalar_source(_prefix_hit_rate,
                               gauge_keys=("prefix_cache_hit_rate",),
                               prefix="dlti_")

    def _spec_scalars() -> dict:
        # Speculative-decode scrape surface (SPEC_METRIC_NAMES contract):
        # explicit *_total counters for the raw draft economics plus two
        # derived gauges — cumulative acceptance ratio and the draft
        # length the adaptive ladder picked for the last decode round.
        # Derivations read the stats dict (aggregated by every engine
        # facade); draft_len is engine-local state, so facades without it
        # (replicated/disagg/fleet fronts, test fakes) expose 0.
        eng = async_engine.engine
        s = eng.stats
        p = s.get("spec_proposed", 0)
        return {
            "spec_proposed_total": p,
            "spec_accepted_total": s.get("spec_accepted", 0),
            "spec_paused_rounds_total": s.get("spec_paused_rounds", 0),
            "spec_acceptance_rate":
                s.get("spec_accepted", 0) / p if p else 0.0,
            "spec_draft_len": getattr(eng, "spec_draft_len", 0),
        }

    registry.add_scalar_source(
        _spec_scalars,
        gauge_keys=("spec_acceptance_rate", "spec_draft_len"),
        prefix="dlti_")
    # Goodput ledger + critical-path attribution (telemetry.ledger):
    # module-level like the watchdog/flight counters — the per-request
    # phase totals back the TTFT decomposition on /metrics, and an
    # in-process trainer's goodput fraction/MFU ride the same registry.
    from dlti_tpu.telemetry import ledger as _ledger

    for metric in (_ledger.goodput_fraction_gauge,
                   _ledger.goodput_seconds_total,
                   _ledger.goodput_mfu_gauge,
                   _ledger.phase_seconds_total,
                   _ledger.phase_requests_total):
        registry.register(metric)
    # HBM memory ledger (telemetry.memledger): per-owner device-memory
    # gauges — module-level like the watchdog/flight counters, so an
    # in-process trainer's ledger and the engine's share one exposition.
    from dlti_tpu.telemetry import memledger as _ml

    for metric in (_ml.hbm_bytes_gauge, _ml.hbm_peak_gauge,
                   _ml.hbm_headroom_gauge, _ml.hbm_untracked_gauge,
                   _ml.remat_kept_blocks_gauge):
        registry.register(metric)
    # SLO engine (telemetry.slo): compliance / error-budget / burn-rate
    # gauges — module-level like the watchdog/flight counters, populated
    # only when a tracker is wired (empty children cost nothing on
    # exposition).
    from dlti_tpu.telemetry import slo as _slo

    for metric in (_slo.compliance_gauge, _slo.budget_remaining_gauge,
                   _slo.burn_rate_gauge):
        registry.register(metric)
    # Durable-writer health (utils.durable_io): free bytes on the
    # persistence filesystem plus path_class-labeled write-error /
    # degraded series — the watchdog's disk_pressure inputs on /metrics.
    from dlti_tpu.utils import durable_io as _dio

    for metric in (_dio.free_bytes_gauge, _dio.write_errors_total,
                   _dio.degraded_gauge):
        registry.register(metric)
    # Replica lifecycle (serving.lifecycle): quarantine / reinstate /
    # flap / migration counters plus the per-replica state gauge —
    # module-level so every fleet in the process (both disagg pools)
    # shares one exposition.
    from dlti_tpu.serving import lifecycle as _lc

    for metric in (_lc.quarantines_total, _lc.reinstates_total,
                   _lc.flaps_total, _lc.migrations_total,
                   _lc.migration_fallbacks_total, _lc.replica_state_gauge):
        registry.register(metric)
    # Disaggregated serving (serving.disagg): per-pool gauges + KV-handoff
    # counters ride in via the controller's pool_scalars source, plus the
    # module-level handoff-latency histogram.
    if hasattr(async_engine.engine, "pool_scalars"):
        from dlti_tpu.serving import disagg as _disagg

        registry.add_scalar_source(async_engine.engine.pool_scalars,
                                   gauge_keys=_disagg.POOL_GAUGE_KEYS,
                                   prefix="dlti_")
        registry.register(_disagg.handoff_seconds)
    # Multi-process fleet (serving.fleet): per-worker federated series
    # (dlti_fleet_w{i}_*) + fleet-level gauges ride in via the
    # supervisor's fleet_scalars source; the module-level wire-protocol
    # and respawn counters register alongside.
    if hasattr(async_engine.engine, "fleet_scalars"):
        from dlti_tpu.serving import fleet as _fleet
        from dlti_tpu.serving import wire as _wire

        registry.add_scalar_source(
            async_engine.engine.fleet_scalars,
            gauge_keys=tuple(async_engine.engine.fleet_gauge_keys),
            prefix="dlti_")
        for metric in (_wire.frames_total, _wire.wire_bytes_total,
                       _fleet.workers_alive_gauge, _fleet.respawns_total):
            registry.register(metric)
    return registry


def llama2_chat_prompt(messages: List[dict]) -> str:
    """Messages -> Llama-2 chat string (the reference's training format,
    ``scripts/prepare_dataset.py:12-25``), so serve-time prompts match the
    fine-tuning distribution."""
    system = ""
    turns: List[Tuple[str, str]] = []  # (user, assistant?) pairs
    pending_user: Optional[str] = None
    for m in messages:
        role, content = m.get("role"), m.get("content", "")
        if role == "system":
            system = content
        elif role == "user":
            if pending_user is not None:
                turns.append((pending_user, ""))
            pending_user = content
        elif role == "assistant":
            turns.append((pending_user or "", content))
            pending_user = None
    if pending_user is not None:
        turns.append((pending_user, None))

    out = []
    first = True
    for user, assistant in turns:
        u = user
        if first and system:
            u = f"<<SYS>>\n{system}\n<</SYS>>\n\n{user}"
        first = False
        if assistant is None:
            out.append(f"[INST] {u} [/INST]")
        else:
            out.append(f"[INST] {u} [/INST] {assistant}")
    return " ".join(out)


class AsyncEngine:
    """Thread-safe facade: a single stepper thread drives the engine;
    callers get a per-request event queue for streaming."""

    def __init__(self, engine: InferenceEngine):
        self.engine = engine
        self.logger = get_logger()
        # The stepper's phase clock: the engine's own (its step phases book
        # into it), so that the loop round the step and the step's inside
        # are one account (telemetry.ledger.StepperAccount).
        self.account = engine.telemetry.stepper
        self.account.describe = lambda: {
            "live_slots": self.engine.num_active,
            "waiting": len(self.engine.waiting)}
        self.account.steps_done = lambda: self.engine.stats["decode_steps"]
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._queues: Dict[str, queue.Queue] = {}
        self._seen: Dict[str, int] = {}
        self._stop = False
        self._dead = False  # set when even fault recovery failed
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="dlti-engine-stepper")
        self._thread.start()

    @property
    def dead(self) -> bool:
        """True once even fault recovery failed and the stepper parked
        (every future submit raises; ``/health`` must stop reporting ok)."""
        return self._dead

    def submit(self, prompt_ids: List[int], params: SamplingParams,
               request_id: Optional[str] = None,
               q: Optional[queue.Queue] = None,
               affinity_key: Optional[str] = None,
               adapter: str = "",
               trace_id: str = "",
               ) -> Tuple[Request, queue.Queue]:
        """Enqueue a request; returns (request, event queue).

        Queue events: ``("token", token_id, logprob)`` per generated token,
        then ``("done", finish_reason)`` — or ``("error", message)``.
        ``q`` lets a caller that pre-created the consumer queue (the
        admission gateway hands it to the HTTP handler before dispatch)
        receive events on its own instance. ``affinity_key`` rides through
        to the engine's submit (session/prefix replica stickiness — a
        no-op on a single engine); ``adapter`` names the LoRA adapter the
        request decodes under ("" = shared base).
        """
        q = q if q is not None else queue.Queue()
        with self._work:
            if self._dead:
                raise RuntimeError(
                    "engine is down (unrecoverable step fault)")
            req = self.engine.submit(
                prompt_ids, params, request_id,
                **({"affinity_key": affinity_key} if affinity_key else {}),
                **({"adapter": adapter} if adapter else {}),
                **({"trace_id": trace_id} if trace_id else {}))
            self._queues[req.request_id] = q
            self._seen[req.request_id] = 0
            self._work.notify()
        return req, q

    def shutdown(self) -> None:
        with self._work:
            self._stop = True
            self._work.notify()
        self._thread.join(timeout=10)

    def _run(self) -> None:
        # The phases below, with the engine's own, cover the stepper's
        # whole loop: they book into the account always, and while the
        # tracer is enabled each is its span of the same name, so whatever
        # the chip waits for between two programs is inside one of them
        # (benchmark/lib/attribute_idle.py reads them). Taking ``_work``
        # is a phase of its own: the handler threads' submits take it too.
        self.account.bind()
        phase = self.account.phase
        while True:
            with phase("server/lock_wait"):
                self._work.acquire()
            try:
                while not self._stop and not self.engine.has_work:
                    with phase("server/wait_work", kind=WAIT):
                        if getattr(self.engine, "lifecycle_pending", False):
                            # A quarantined replica awaits its probe or a
                            # rolling reload is in flight: poll instead of
                            # parking, so the fleet's lifecycle tick runs
                            # even on an idle server (a no-work step() is
                            # just the tick). Engines without a lifecycle
                            # keep the legacy untimed park.
                            self._work.wait(timeout=0.05)
                            break
                        self._work.wait()
                if self._stop:
                    for q in self._queues.values():
                        q.put(("error", "server shutting down"))
                    # The engine leaves a decode round in flight between
                    # two steps, and abort_all waits for it: no program
                    # runs and no device array is owed when the process
                    # exits.
                    self.engine.abort_all(reason="shutdown")
                    return
            finally:
                self._work.release()
            # Step OUTSIDE the lock: one step is a compiled-program call
            # (a long prefill runs for seconds), and holding the lock across
            # it serializes every HTTP submit against the device, which
            # shows as low slot occupancy under load. Concurrent
            # engine.submit() only
            # appends to the waiting deque (GIL-atomic) and touches its
            # own stats key; admission consumes the deque at one point
            # inside step(), so a racing submit lands this step or next.
            try:
                with phase("server/step", step=True):
                    self.engine.step()
            except Exception as e:  # surface engine faults to the waiters
                self.logger.exception("engine step failed")
                rec = get_recorder()
                if rec is not None:
                    # Black box first, cleanup second: abort_all below
                    # rewrites the very state (slots, waiting, stats) the
                    # forensics need.
                    from dlti_tpu.telemetry.memledger import is_oom_error
                    rec.dump(reason="oom" if is_oom_error(e)
                             else "engine_step_fault", exc=e, force=True)
                with self._work:
                    # Fail fast: abort every request the engine holds
                    # (slots + waiting; KV is NOT prefix-cache-registered
                    # — it may never have been written) and error EVERY
                    # registered consumer, including requests that
                    # finished during the failing step and any submit()
                    # that raced into the fault window (engine state is
                    # suspect; one clean 500, client may retry). The
                    # engine ends empty: no hot-loop on a persistent
                    # fault, no decoding into deleted queues.
                    try:
                        self.engine.abort_all(reason="error")
                    except Exception:
                        # Even the abort failed — bookkeeping is beyond
                        # recovery; park the stepper and fail all future
                        # submits instead of serving from a corrupt
                        # engine while /health looks ok.
                        self.logger.exception(
                            "engine abort failed; stepper parked")
                        self._dead = True
                        self._stop = True
                    for q in self._queues.values():
                        q.put(("error", f"{type(e).__name__}: {e}"))
                    self._queues.clear()
                    self._seen.clear()
                    if self._stop:
                        return
                continue
            with phase("server/lock_wait"):
                self._work.acquire()
            try:
                with phase("server/drain_events"):
                    self._drain_events()
            finally:
                self._work.release()

    def _drain_events(self) -> None:
        """Push tokens generated since the last step to per-request queues."""
        live = list(self.engine.slots)
        reqs = [s.request for s in live if s.request is not None]
        reqs.extend(r for r in list(self.engine.finished)
                    if r.request_id in self._queues)
        for req in reqs:
            q = self._queues.get(req.request_id)
            if q is None:
                continue
            seen = self._seen.get(req.request_id, 0)
            for i in range(seen, len(req.output_token_ids)):
                q.put(("token", req.output_token_ids[i], req.output_logprobs[i]))
            self._seen[req.request_id] = len(req.output_token_ids)
            if req.done:
                if req.finish_reason == "error":
                    # Replica failover exhausted its retries (or no
                    # survivors): this one request failed, fleet stays up.
                    q.put(("error", "request failed: replica fault, "
                                    "retries exhausted"))
                else:
                    q.put(("done", req.finish_reason))
                del self._queues[req.request_id]
                del self._seen[req.request_id]


@dataclass
class ServerConfig:
    host: str = "0.0.0.0"
    port: int = 8000
    model_name: str = "dlti-tpu-model"
    request_timeout_s: float = 600.0
    default_params: SamplingParams = field(default_factory=SamplingParams)
    # Admission gateway (dlti_tpu.serving.gateway): None or disabled keeps
    # the legacy direct-admission path byte-for-byte.
    gateway: Optional["GatewayConfig"] = None
    # Self-monitoring (dlti_tpu.telemetry): trace_dir feeds the on-demand
    # POST /debug/profile capture; the watchdog / flight_recorder blocks
    # enable the anomaly rules and the black-box dumps. None keeps only
    # the always-on /debug/vars sampler + /dashboard.
    telemetry: Optional["TelemetryConfig"] = None


class _Handler(BaseHTTPRequestHandler):
    """One instance per connection (ThreadingHTTPServer)."""

    server_version = "dlti-tpu"
    protocol_version = "HTTP/1.1"

    # Injected via functools-partial-style subclassing in serve().
    async_engine: AsyncEngine
    tokenizer: Tokenizer
    cfg: ServerConfig
    registry: "MetricsRegistry"
    gateway = None  # AdmissionGateway when ServerConfig.gateway enables it
    sampler = None  # TimeSeriesSampler behind /debug/vars + /dashboard
    slo = None  # SLOTracker behind /debug/slo (telemetry.slo)
    deploy = None  # DeploymentController behind /v1/deploy (serving.deploy)
    profile_lock = None  # threading.Lock guarding POST /debug/profile

    def log_message(self, fmt, *args):  # route through our logger
        get_logger().debug("http: " + fmt, *args)

    # -- helpers -------------------------------------------------------
    def _json(self, code: int, obj: dict,
              headers: Optional[dict] = None) -> None:
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _error(self, code: int, message: str,
               retry_after: Optional[float] = None) -> None:
        headers = None
        if retry_after is not None:
            # Integral seconds per RFC 9110 §10.2.3, never rounded to 0 —
            # a 429 whose Retry-After says "now" just invites the same
            # overload back immediately.
            headers = {"Retry-After": str(max(1, int(-(-retry_after // 1))))}
        err_type = ("rate_limit_error" if code == 429
                    else "overloaded_error" if code == 503
                    else "invalid_request_error")
        self._json(code, {"error": {"message": message, "type": err_type}},
                   headers=headers)

    def _read_body(self) -> Optional[dict]:
        try:
            n = int(self.headers.get("Content-Length", 0))
            return json.loads(self.rfile.read(n) or b"{}")
        except (ValueError, json.JSONDecodeError):
            self._error(400, "invalid JSON body")
            return None

    class _StopMatcher:
        """Stateful windowed stop-string scanner shared by the stream and
        non-stream paths (one implementation of the window arithmetic, so
        the two cannot diverge): feed(text) -> (cut, safe) with the scan
        window advanced past already-scanned text."""

        def __init__(self, stops: tuple):
            self.stops = stops
            self._max = max((len(s) for s in stops), default=0)
            self._prev = 0

        def feed(self, text: str) -> tuple:
            cut, safe = _Handler._scan_stops(
                text, self.stops, start=self._prev - self._max + 1)
            self._prev = len(text)
            return cut, safe

    @staticmethod
    def _scan_stops(text: str, stops: tuple, start: int = 0) -> tuple:
        """(cut, safe): ``cut`` is the index of the earliest stop-string
        match (None if absent); ``safe`` is how much of ``text`` may be
        emitted now — held back so a stop string arriving across token
        boundaries is never partially streamed and then impossible to
        retract (the OpenAI contract excludes the stop string from the
        returned text). ``start`` windows the search: a caller scanning
        per token passes the previous length minus the longest stop, so
        the total scan work stays linear in the output length."""
        cut = None
        for s in stops:
            i = text.find(s, max(0, start))
            if i != -1 and (cut is None or i < cut):
                cut = i
        if cut is not None:
            return cut, cut
        hold = 0
        for s in stops:
            for k in range(1, len(s)):
                if text.endswith(s[:k]):
                    hold = max(hold, k)
        return None, len(text) - hold

    @staticmethod
    def _stops_from(body: dict) -> tuple:
        """OpenAI ``stop``: a string or list of strings (<= 4)."""
        stop = body.get("stop")
        if stop is None:
            return ()
        if isinstance(stop, str):
            stop = [stop]
        if (not isinstance(stop, list) or len(stop) > 4
                or not all(isinstance(s, str) and s for s in stop)):
            raise ValueError(
                "stop must be a non-empty string or a list of up to 4")
        return tuple(stop)

    def _params_from(self, body: dict) -> SamplingParams:
        # Every client-supplied field is cast here, before the request
        # reaches the engine stepper thread — a malformed value must fail
        # this one request with a 400, not error out every in-flight one.
        d = self.cfg.default_params
        stop_ids = tuple(int(t) for t in body.get("stop_token_ids", ()))
        seed = body.get("seed")
        return SamplingParams(
            temperature=float(body.get("temperature", d.temperature)),
            top_k=int(body.get("top_k", d.top_k)),
            top_p=float(body.get("top_p", d.top_p)),
            max_tokens=int(body.get("max_tokens", d.max_tokens)),
            stop_token_ids=stop_ids,
            seed=int(seed) if seed is not None else None,
            logprobs=bool(body.get("logprobs", False)),
        )

    # -- routes --------------------------------------------------------
    def do_GET(self):
        path, _, query = self.path.partition("?")
        if path == "/debug/vars":
            # Time-series ring snapshot (JSON): every registry scalar +
            # histogram summary, sampled on a cadence — what the
            # /dashboard page and the loadgen's end-of-run scrape read.
            if self.sampler is None:
                return self._error(404, "no time-series sampler")
            tail = None
            if query.startswith("tail="):
                try:
                    tail = max(1, int(query[5:]))
                except ValueError:
                    return self._error(400, "tail must be an integer")
            return self._json(200, self.sampler.snapshot(tail))
        if path == "/debug/slow":
            # Critical-path attribution (telemetry.ledger): the K worst
            # requests retained with their full phase timelines — "why
            # was this p99 request slow: queue, prefill, tier restore,
            # or failover?" answered without a trace viewer.
            cp = self.async_engine.engine.telemetry.critical_path
            n = None
            if query.startswith("n="):
                try:
                    n = max(1, int(query[2:]))
                except ValueError:
                    return self._error(400, "n must be an integer")
            worst = cp.slow.worst(n)
            return self._json(200, {
                "k": cp.slow.k, "retained": len(cp.slow),
                "phases": list(_REQUEST_PHASES),
                "worst": worst,
            })
        if path == "/debug/trace":
            # Chrome-trace snapshot — the process-global tracer merged
            # with every fleet worker's federated span tail (already
            # rebased onto this process's clock), one pid per source so
            # Perfetto renders a multi-process timeline. With
            # ?request_id= (optionally &latency_s=<client-observed>):
            # the merged, clock-aligned span tree for ONE request across
            # all processes, with per-leg durations and the residual.
            tracer = get_tracer()
            fed = getattr(self.async_engine.engine, "trace", None)
            if not tracer.enabled and fed is None:
                return self._error(404, "tracing disabled (start the "
                                        "server with --trace-dir)")
            qp = {}
            for part in query.split("&"):
                k, _, v = part.partition("=")
                if k:
                    qp[k] = v
            rid = qp.get("request_id", "")
            if not rid:
                if fed is not None:
                    return self._json(200, fed.merged_dict(
                        tracer if tracer.enabled else None))
                return self._json(200, tracer.to_dict())
            from dlti_tpu.telemetry.distributed_trace import (
                request_timeline,
            )

            latency = None
            if qp.get("latency_s"):
                try:
                    latency = float(qp["latency_s"])
                except ValueError:
                    return self._error(400, "latency_s must be a float")
            events = list(fed.events()) if fed is not None else []
            if tracer.enabled:
                events.extend(tracer.events())
            tl = request_timeline(events, rid, client_latency_s=latency)
            if not tl["spans"]:
                return self._error(404, f"no spans retained for request "
                                        f"{rid!r} (ring evicted, or id "
                                        f"unknown)")
            return self._json(200, tl)
        if path == "/debug/slo":
            # Declared objectives vs reality (telemetry.slo): per-
            # (objective, class) compliance, error budget remaining,
            # burn rates per alert window, breaching tiers — the JSON
            # twin of the flight dump's slo.json, and what loadgen's
            # LoadReport.slo cross-checks itself against.
            if self.slo is None:
                return self._error(404, "slo engine disabled (start the "
                                        "server with --slo)")
            return self._json(200, self.slo.to_dict())
        if path == "/debug/memory":
            # Full "where the memory lives" map (telemetry.memledger):
            # per-owner bytes, untracked/residual buckets summing to
            # bytes-in-use, activation-peak estimate, top untracked
            # arrays — the JSON twin of the flight dump's memory.json.
            ledger = getattr(self.async_engine.engine, "memledger", None)
            if ledger is None or not ledger.enabled:
                return self._error(404, "memory ledger disabled")
            return self._json(200, ledger.to_dict(top_k=8))
        if path == "/dashboard":
            # Self-contained live dashboard: inline CSS/JS polling
            # /debug/vars — watching a run needs a browser, not a
            # Prometheus deployment.
            body = render_dashboard_html().encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/html; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        if self.path == "/health":
            # Load-balancer truth: a parked stepper or a draining gateway
            # must read unhealthy so traffic routes elsewhere — 200 here
            # while submits 503 kept corpses in rotation.
            body = {}
            eng = self.async_engine.engine
            counts = getattr(eng, "lifecycle_counts", None)
            if counts is not None:
                # Fleet lifecycle detail: "quarantined" replicas are
                # healing (probe pending) and expected back; "dead" ones
                # are gone for good — a balancer weighs them differently.
                body.update(counts())
            states = getattr(eng, "worker_states", None)
            if states is not None:
                # Multi-process fleet: per-worker liveness
                # (live/quarantined/draining/respawning/dead).
                body["workers"] = states()
            if self.async_engine.dead:
                self._json(503, {"status": "dead", **body})
            elif self.gateway is not None and self.gateway.draining:
                self._json(503, {"status": "draining", **body})
            elif states is not None and not any(
                    s == "live" for s in body["workers"].values()):
                # No worker live: unhealthy — but a respawn may be
                # imminent, so advertise its backoff as Retry-After
                # (a degraded fleet with ANY live worker stays 200).
                headers = {}
                ra = getattr(eng, "respawn_retry_after_s", 0.0)
                if ra > 0:
                    headers["Retry-After"] = str(max(1, int(-(-ra // 1))))
                self._json(503, {"status": "no_live_workers", **body},
                           headers=headers)
            else:
                self._json(200, {"status": "ok", **body})
        elif self.path == "/stats":
            # Raw engine counters/gauges + request-latency histogram
            # summaries (count/sum/mean/p50/p90/p99), all served from the
            # shared MetricsRegistry.
            self._json(200, self.registry.stats_dict())
        elif self.path == "/metrics":
            # Prometheus text exposition (vLLM-parity observability),
            # rendered from the shared MetricsRegistry: the legacy
            # dlti_<stat> counters/gauges byte-for-byte, plus the
            # request-lifecycle histograms (TTFT/TPOT/queue time).
            body = self.registry.render_prometheus().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif self.path == "/v1/deploy":
            # Continuous-delivery state (serving.deploy): incumbent
            # step/digest, canary in flight, refused steps, gate verdict
            # of the last candidate — the JSON twin of the flight dump's
            # deploy.json.
            if self.deploy is None:
                return self._error(404, "deploy controller disabled "
                                        "(start the server with "
                                        "--deploy-watch)")
            self._json(200, self.deploy.status())
        elif self.path == "/v1/models":
            self._json(200, {"object": "list", "data": [{
                "id": self.cfg.model_name, "object": "model",
                "owned_by": "dlti_tpu",
            }]})
        elif self.path == "/v1/adapters":
            # Registered adapter names (process-global catalog) — what a
            # client may put in X-Adapter right now.
            from dlti_tpu.serving.adapters import get_catalog

            self._json(200, {"object": "list",
                             "data": get_catalog().names()})
        else:
            self._error(404, f"no route {self.path}")

    def do_POST(self):
        if self.path == "/v1/completions":
            self._completions(chat=False)
        elif self.path == "/v1/chat/completions":
            self._completions(chat=True)
        elif self.path == "/v1/adapters":
            self._register_adapter()
        elif self.path == "/v1/reload":
            self._reload_weights()
        elif self.path == "/v1/deploy":
            self._deploy_control()
        elif self.path == "/debug/profile":
            self._profile()
        else:
            self._error(404, f"no route {self.path}")

    def _reload_weights(self) -> None:
        """Zero-downtime rolling weight upgrade:
        ``POST /v1/reload {"directory": d}`` where ``d`` is a params
        export written by ``checkpoint.store.save_pytree`` (the same
        artifact class adapters hot-load from). The fleet hot-swaps the
        weights one replica at a time — drain via live KV migration,
        rebuild, canary, reinstate — so clients never see an error. The
        artifact is digest-verified on the stepper thread before any
        replica swaps; 409 while a roll is already in progress; 400 when
        the engine has no lifecycle support (single-engine servers
        restart instead)."""
        body = self._read_body()
        if body is None:
            return
        directory = str(body.get("directory", "") or "")
        if not directory:
            return self._error(400, "directory is required")
        if not os.path.isfile(os.path.join(directory, "MANIFEST.json")):
            return self._error(
                400, f"{directory!r} is not a checkpoint-store params "
                     f"export (no MANIFEST.json)")
        request_reload = getattr(self.async_engine.engine,
                                 "request_reload", None)
        if request_reload is None:
            return self._error(
                400, "engine has no replica lifecycle (rolling reload "
                     "needs a replicated fleet; restart single-engine "
                     "servers instead)")
        from dlti_tpu.checkpoint.store import (
            load_pytree, manifest_digest, verify_pytree_dir,
        )

        def _provider():
            # Runs once on the stepper thread: digest-verified load — a
            # corrupt artifact aborts the roll before any replica swaps.
            return load_pytree(directory, verify=True)

        # Pin the digest NOW, then re-verify immediately before EVERY
        # per-replica swap: an artifact corrupted mid-roll (bit rot, a
        # re-export racing the roll) aborts the remaining swaps instead
        # of shipping different bytes to different replicas.
        expect_digest = manifest_digest(directory)

        def _verify() -> bool:
            if manifest_digest(directory) != expect_digest:
                return False
            return verify_pytree_dir(directory)[0]

        if not request_reload(_provider, verify=_verify):
            return self._error(409, "a rolling reload is already in "
                                    "progress")
        with self.async_engine._work:
            self.async_engine._work.notify()  # wake an idle stepper
        self._json(200, {"status": "reloading", "directory": directory})

    def _deploy_control(self) -> None:
        """Operator switch for the continuous-delivery pipeline:
        ``POST /v1/deploy {"enabled": bool}``. Disabling cancels any
        in-flight canary without judging it (the step stays eligible);
        enabling resumes the watch loop. 404 when no controller is
        wired (start the server with ``--deploy-watch``)."""
        if self.deploy is None:
            return self._error(404, "deploy controller disabled (start "
                                    "the server with --deploy-watch)")
        body = self._read_body()
        if body is None:
            return
        if "enabled" not in body:
            return self._error(400, "enabled is required")
        self.deploy.set_enabled(bool(body["enabled"]))
        self._json(200, self.deploy.status())

    def _register_adapter(self) -> None:
        """Hot-register a trained adapter checkpoint with zero restart:
        ``POST /v1/adapters {"name": n, "directory": d}``. The directory
        is digest-verified through the checkpoint store before the name
        exists; a corrupt checkpoint is quarantined and 400s here — the
        name stays unknown, so completions keep 404ing it."""
        body = self._read_body()
        if body is None:
            return
        name = str(body.get("name", "") or "")
        directory = str(body.get("directory", "") or "")
        if not name or not directory:
            return self._error(400, "name and directory are required")
        from dlti_tpu.serving.adapters import AdapterError, register_adapter

        try:
            register_adapter(name, directory)
        except AdapterError as e:
            return self._error(400, str(e))
        self._json(200, {"object": "adapter", "name": name,
                         "directory": directory})

    def _profile(self) -> None:
        """On-demand ``jax.profiler`` capture around the live engine:
        ``POST /debug/profile {"seconds": s}`` writes a device trace into
        the configured ``--trace-dir`` (the trainer has its profile
        window flags; this is serving's equivalent, without a restart).
        One capture at a time — concurrent requests get 409."""
        body = self._read_body()
        if body is None:
            return
        trace_dir = (self.cfg.telemetry.trace_dir
                     if self.cfg.telemetry is not None else "")
        if not trace_dir:
            return self._error(
                400, "profiling needs a trace dir: start the server with "
                     "--trace-dir")
        try:
            seconds = float(body.get("seconds", 3.0))
        except (TypeError, ValueError):
            return self._error(400, "seconds must be a number")
        if not 0.0 < seconds <= 120.0:
            return self._error(400, "seconds must be in (0, 120]")
        if self.profile_lock is None or not self.profile_lock.acquire(
                blocking=False):
            # jax.profiler is process-global: a second start_trace would
            # raise (or corrupt the first capture), so refuse loudly.
            return self._error(409, "a profile capture is already running")
        try:
            out_dir = os.path.join(trace_dir, "serve_profile")
            t0 = time.monotonic()
            # Through the tracer, so that the capture carries the
            # program's spans beside the device's operations.
            tracer = get_tracer()
            tracer.start_capture(out_dir)
            try:
                time.sleep(seconds)
            finally:
                tracer.stop_capture()
            self._json(200, {"status": "ok", "trace_dir": out_dir,
                             "seconds": round(time.monotonic() - t0, 3)})
        except Exception as e:  # profiler backends vary; fail this request
            self._error(500, f"profiler: {type(e).__name__}: {e}")
        finally:
            self.profile_lock.release()

    # -- completion core ----------------------------------------------
    def _completions(self, chat: bool) -> None:
        body = self._read_body()
        if body is None:
            return
        tok = self.tokenizer
        if chat:
            messages = body.get("messages")
            if not isinstance(messages, list) or not messages:
                return self._error(400, "messages must be a non-empty list")
            prompt = llama2_chat_prompt(messages)
        else:
            prompt = body.get("prompt", "")
            if isinstance(prompt, list):
                prompt = prompt[0] if prompt else ""
            if not isinstance(prompt, str) or not prompt:
                return self._error(400, "prompt must be a non-empty string")

        prompt_ids = tok.encode(prompt, add_bos=True)
        try:
            params = self._params_from(body)
            stops = self._stops_from(body)
        except (TypeError, ValueError) as e:
            return self._error(400, f"invalid sampling parameter: {e}")
        max_len = self.async_engine.engine.cfg.max_model_len
        if len(prompt_ids) >= max_len:
            return self._error(400, f"prompt has {len(prompt_ids)} tokens; "
                                    f"max_model_len is {max_len}")

        try:
            n = int(body.get("n", 1))
        except (TypeError, ValueError):
            return self._error(400, "n must be an integer")
        if not 1 <= n <= self.async_engine.engine.cfg.max_seqs:
            return self._error(
                400, f"n must be in [1, {self.async_engine.engine.cfg.max_seqs}]")
        if n > 1 and body.get("stream"):
            return self._error(400, "n > 1 does not support stream=true")
        if n > 1 and (params.temperature == 0.0 or params.top_k == 1):
            return self._error(
                400, "n > 1 with deterministic sampling (temperature=0 or "
                     "top_k=1) would return n identical choices; relax the "
                     "sampling or drop n")

        # Multi-LoRA routing: X-Adapter header first (works with AND
        # without a gateway), else the gateway's tenant→adapter map.
        # Unknown names 404 HERE, before any queue/slot is consumed —
        # the engine only ever sees catalog-registered adapters.
        adapter = str(self.headers.get("X-Adapter", "") or "").strip()
        if adapter:
            from dlti_tpu.serving.adapters import get_catalog

            if adapter not in get_catalog():
                return self._error(
                    404, f"unknown adapter {adapter!r}: register it via "
                         "POST /v1/adapters first")

        # Admission metadata (gateway only): tenant from headers, priority
        # class + queued-deadline from the body. Validated before submit so
        # a bad value 400s this request, same contract as sampling params.
        tenant = priority = None
        deadline_s = 0.0
        affinity_key = None
        if self.gateway is not None:
            tenant = tenant_from_headers(
                self.headers, self.gateway.cfg.default_tenant)
            priority = str(body.get("priority")
                           or self.headers.get("X-Priority")
                           or "interactive")
            if priority not in PRIORITIES:
                return self._error(
                    400, f"priority must be one of {PRIORITIES}")
            try:
                deadline_s = float(body.get("deadline_s", 0) or 0)
            except (TypeError, ValueError):
                return self._error(400, "deadline_s must be a number")
            if not adapter:
                adapter = self.gateway.adapter_for(tenant)
            if self.gateway.cfg.affinity:
                # Cache-affinity routing: a session (X-Session) or
                # hashed prompt-prefix key makes repeat traffic land on
                # the replica whose prefix cache is already warm. The
                # adapter id is part of the key: adapter A's warm KV is
                # useless to adapter B.
                affinity_key = affinity_key_from(
                    self.headers, prompt_ids,
                    self.gateway.cfg.affinity_prefix_tokens,
                    adapter=adapter)

        def _submit(p_ids, p, rid_):
            if self.gateway is not None:
                return self.gateway.submit(
                    p_ids, p, rid_, tenant=tenant, priority=priority,
                    deadline_s=deadline_s, affinity_key=affinity_key,
                    adapter=adapter)
            return self.async_engine.submit(
                p_ids, p, rid_,
                **({"adapter": adapter} if adapter else {}))

        rid = ("chatcmpl-" if chat else "cmpl-") + uuid.uuid4().hex[:24]
        created = int(time.time())
        try:
            if n == 1:
                req, q = _submit(prompt_ids, params, rid)
            else:
                # n choices = n engine requests decoding CONCURRENTLY in
                # the continuous batch (they share prefill via the prefix
                # cache). A user seed derives per-choice seeds so the
                # response stays reproducible without n identical samples.
                subs = []
                try:
                    for i in range(n):
                        p_i = params if params.seed is None else \
                            dataclasses.replace(params, seed=params.seed + i)
                        subs.append(_submit(prompt_ids, p_i, f"{rid}-{i}"))
                except Exception:
                    # A submit failed mid-loop (e.g. the stepper parked
                    # between choices): early-cancel every choice already
                    # submitted, or they decode to max_tokens into queues
                    # nobody reads — the orphan burn the disconnect/stop
                    # cancels exist to prevent.
                    for other, _ in subs:
                        other.cancel_requested = True
                    raise
        except AdmissionError as e:  # gateway refusal: 429/503 + Retry-After
            return self._error(e.status, e.message, retry_after=e.retry_after)
        except ValueError as e:
            return self._error(400, str(e))
        except RuntimeError as e:  # engine parked after unrecoverable fault
            return self._error(503, str(e))

        if body.get("stream"):
            self._stream_response(req, q, chat, created, stops)
        elif n == 1:
            self._full_response(req, q, chat, created, stops)
        else:
            self._multi_response(subs, rid, chat, created, stops)

    def _collect(self, q: queue.Queue, req: Optional[Request] = None):
        """Yield events until done/error/reject/timeout.

        On timeout the request is early-cancelled first (same contract as
        the disconnect/stop cancels): without it a timed-out request kept
        decoding to max_tokens into a queue nobody reads, burning a slot
        live requests were waiting for."""
        deadline = time.monotonic() + self.cfg.request_timeout_s
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                if req is not None:
                    req.cancel_requested = True
                yield ("error", "request timed out")
                return
            try:
                ev = q.get(timeout=min(remaining, 1.0))
            except queue.Empty:
                continue
            yield ev
            if ev[0] in ("done", "error", "reject"):
                return

    def _collect_choice(self, req: Request, q: queue.Queue,
                        stops: tuple) -> tuple:
        """Drain one non-streaming request to completion: returns
        ((token_ids, logprobs, text, finish), (status, error_message))
        with exactly one of the pair set. THE one
        collect/stop-scan/truncate implementation for the n==1 and n>1
        paths, so they cannot diverge. Stop STRINGS (OpenAI `stop`;
        token-boundary-agnostic, so matched on detokenized text here, not
        in the engine) request early cancel and keep draining until the
        engine's done event so the slot release is observed; the scan is
        windowed past already-scanned text."""
        token_ids: List[int] = []
        logprobs: List[float] = []
        finish = "stop"
        cut = None
        matcher = self._StopMatcher(stops)
        for ev in self._collect(q, req):
            if ev[0] == "token":
                token_ids.append(ev[1])
                logprobs.append(ev[2])
                if stops and cut is None:
                    cut, _ = matcher.feed(self.tokenizer.decode(token_ids))
                    if cut is not None:
                        req.cancel_requested = True
            elif ev[0] == "done":
                finish = ev[1]
            elif ev[0] == "reject":  # gateway shed (e.g. queued deadline)
                # Pass any retry-after hint through to _error (the shed
                # tuple grew a 4th element; older 3-element producers
                # still work).
                return None, tuple(ev[1:])
            else:
                return None, (500, ev[1])
        text = self.tokenizer.decode(token_ids)
        if cut is not None:
            text, finish = text[:cut], "stop"
        return (token_ids, logprobs, text, finish), None

    @staticmethod
    def _phases_of(req) -> Optional[dict]:
        """Server-side critical-path breakdown of a finished request
        (telemetry.ledger): ``{"total_s", "ttft_s", <phase>: s, ...}``.
        None when the engine request isn't resolvable/finished (so a
        refusal path never grows a bogus breakdown)."""
        eng_req = getattr(req, "_req", None) or req
        if getattr(eng_req, "finish_time", None) is None:
            return None
        try:
            b = request_breakdown(eng_req)
        except Exception:  # attribution must never fail a response
            return None
        return {"total_s": b["total_s"], "ttft_s": b["ttft_s"],
                **b["phases"]}

    def _full_response(self, req: Request, q: queue.Queue, chat: bool,
                       created: int, stops: tuple = ()) -> None:
        got, err = self._collect_choice(req, q, stops)
        if err is not None:
            return self._error(*err)
        token_ids, logprobs, text, finish = got
        usage = {
            "prompt_tokens": len(req.prompt_token_ids),
            "completion_tokens": len(token_ids),
            "total_tokens": len(req.prompt_token_ids) + len(token_ids),
        }
        if chat:
            choice = {"index": 0, "message": {"role": "assistant", "content": text},
                      "finish_reason": finish}
            obj = "chat.completion"
        else:
            choice = {"index": 0, "text": text, "finish_reason": finish}
            obj = "text_completion"
        if req.params.logprobs:
            choice["logprobs"] = {"token_logprobs": logprobs,
                                  "tokens": token_ids}
        out = {
            "id": req.request_id, "object": obj, "created": created,
            "model": self.cfg.model_name, "choices": [choice], "usage": usage,
        }
        phases = self._phases_of(req)
        if phases is not None:
            # Server-side phase attribution (gateway queue, engine queue,
            # tier restore, prefill, failover, decode): lets a client —
            # and the loadgen — decompose the latency it observed.
            out["phases"] = phases
        eng_req = getattr(req, "_req", None) or req
        # Lifecycle visibility: how many times this request was live-
        # migrated (paged-KV handoff mid-decode) or failover-resubmitted
        # — rolling-restart drills assert "zero errors AND the migrations
        # actually happened".
        out["migrations"] = getattr(eng_req, "num_migrations", 0)
        out["retries"] = getattr(eng_req, "num_retries", 0)
        # Trace context: lets the client (and the loadgen) fetch the
        # merged cross-process timeline via /debug/trace?request_id=.
        out["trace_id"] = getattr(eng_req, "trace_id", "")
        self._json(200, out)

    def _multi_response(self, subs: list, rid: str, chat: bool,
                        created: int, stops: tuple = ()) -> None:
        """OpenAI ``n`` > 1: the n requests decode concurrently in the
        continuous batch (submitted before this runs); collect each in
        turn — later queues buffer while earlier ones drain."""
        choices = []
        total_completion = 0
        prompt_tokens = len(subs[0][0].prompt_token_ids)
        for i, (req, q) in enumerate(subs):
            got, err = self._collect_choice(req, q, stops)
            if err is not None:
                # One choice failed/timed out: early-cancel every other
                # still-running choice before erroring — without this the
                # remaining n-1 requests decode to max_tokens into queues
                # nobody reads (the orphan-burn disconnect-cancel exists
                # to prevent).
                for other, _ in subs:
                    other.cancel_requested = True
                return self._error(*err)
            token_ids, logprobs, text, finish = got
            total_completion += len(token_ids)
            if chat:
                choice = {"index": i,
                          "message": {"role": "assistant", "content": text},
                          "finish_reason": finish}
            else:
                choice = {"index": i, "text": text, "finish_reason": finish}
            if req.params.logprobs:
                choice["logprobs"] = {"token_logprobs": logprobs,
                                      "tokens": token_ids}
            choices.append(choice)
        self._json(200, {
            "id": rid,
            "object": "chat.completion" if chat else "text_completion",
            "created": created, "model": self.cfg.model_name,
            "choices": choices,
            "usage": {"prompt_tokens": prompt_tokens,
                      "completion_tokens": total_completion,
                      "total_tokens": prompt_tokens + total_completion},
        })

    def _stream_response(self, req: Request, q: queue.Queue, chat: bool,
                         created: int, stops: tuple = ()) -> None:
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        def chunk(data: str) -> None:
            payload = f"data: {data}\n\n".encode()
            self.wfile.write(f"{len(payload):x}\r\n".encode() + payload + b"\r\n")
            self.wfile.flush()

        obj = "chat.completion.chunk" if chat else "text_completion"
        # Incremental detokenization: decode the full id list and emit the
        # suffix, so multi-token unicode never splits mid-character.
        token_ids: List[int] = []
        emitted = ""
        finish = None
        meter = _HandlerMeter.of_this_thread()
        # The whole list is decoded at every token unless the tokenizer
        # can extend its own text by one token (``decode_appended``).
        append = getattr(self.tokenizer, "decode_appended", None)
        decoded = ""
        try:
            if chat:
                chunk(json.dumps({
                    "id": req.request_id, "object": obj, "created": created,
                    "model": self.cfg.model_name,
                    "choices": [{"index": 0, "delta": {"role": "assistant"},
                                 "finish_reason": None}]}))
            cancelled = False
            matcher = self._StopMatcher(stops)
            for ev in self._collect(q, req):
                meter.event()
                if ev[0] == "token":
                    if cancelled:
                        # Stop already matched: drain (the engine finishes
                        # within one decode window of the cancel flag) so
                        # the final usage chunk reads a settled request.
                        continue
                    token_ids.append(ev[1])
                    decoded = text = append(decoded, token_ids) \
                        if append is not None \
                        else self.tokenizer.decode(token_ids)
                    if stops:
                        # Stop strings: emit only up to the earliest match
                        # (the stop string itself is never streamed), and
                        # hold back any tail that could be the start of a
                        # match arriving across token boundaries.
                        cut, safe = matcher.feed(text)
                        if cut is not None:
                            delta = text[len(emitted):cut]
                            emitted += delta
                            if delta:
                                key = "delta" if chat else "text"
                                val = {"content": delta} if chat else delta
                                chunk(json.dumps({
                                    "id": req.request_id, "object": obj,
                                    "created": created,
                                    "model": self.cfg.model_name,
                                    "choices": [{"index": 0, key: val,
                                                 "finish_reason": None}]}))
                            req.cancel_requested = True
                            cancelled = True
                            continue
                        text = text[:safe]
                    delta = text[len(emitted):]
                    emitted += delta
                    if not delta:
                        continue  # partial unicode / held-back stop prefix
                    key = "delta" if chat else "text"
                    val = {"content": delta} if chat else delta
                    chunk(json.dumps({
                        "id": req.request_id, "object": obj, "created": created,
                        "model": self.cfg.model_name,
                        "choices": [{"index": 0, key: val, "finish_reason": None}]}))
                elif ev[0] == "done":
                    finish = "stop" if cancelled else ev[1]
                    if stops and not cancelled:
                        # Flush the held-back tail: the request ended
                        # without a stop match, so the conservative
                        # hold-back (a possible stop prefix) is real
                        # output the client must still receive.
                        tail = self.tokenizer.decode(token_ids)[len(emitted):]
                        if tail:
                            emitted += tail
                            key = "delta" if chat else "text"
                            val = {"content": tail} if chat else tail
                            chunk(json.dumps({
                                "id": req.request_id, "object": obj,
                                "created": created,
                                "model": self.cfg.model_name,
                                "choices": [{"index": 0, key: val,
                                             "finish_reason": None}]}))
                else:
                    # ("error", msg) or a gateway ("reject", status, msg):
                    # headers are already on the wire, so the refusal
                    # arrives as a terminal SSE error frame.
                    chunk(json.dumps({"error": {"message": ev[-1]}}))
                    break
            if finish is not None:
                key = "delta" if chat else "text"
                val = {} if chat else ""
                final = {
                    "id": req.request_id, "object": obj, "created": created,
                    "model": self.cfg.model_name,
                    "choices": [{"index": 0, key: val, "finish_reason": finish}],
                    # Token-accurate usage in the final chunk (OpenAI
                    # stream_options.include_usage semantics, always on):
                    # SSE event count != token count (multi-step decode
                    # batches tokens per sync; detokenization can emit
                    # empty deltas), so load tests need this for honest
                    # streaming throughput numbers.
                    "usage": {
                        "prompt_tokens": len(req.prompt_token_ids),
                        "completion_tokens": len(req.output_token_ids),
                        "total_tokens": len(req.prompt_token_ids)
                        + len(req.output_token_ids),
                    }}
                phases = self._phases_of(req)
                if phases is not None:
                    final["phases"] = phases
                eng_req = getattr(req, "_req", None) or req
                final["migrations"] = getattr(eng_req, "num_migrations", 0)
                final["retries"] = getattr(eng_req, "num_retries", 0)
                final["trace_id"] = getattr(eng_req, "trace_id", "")
                chunk(json.dumps(final))
            chunk("[DONE]")
            self.wfile.write(b"0\r\n\r\n")
            self.wfile.flush()
        except (BrokenPipeError, ConnectionResetError):
            # Early-cancel the orphaned request: without this the engine
            # keeps burning decode windows into a queue nobody reads,
            # up to max_tokens, while live requests wait for the slot.
            req.cancel_requested = True
            get_logger().info("client disconnected mid-stream: %s", req.request_id)


def make_server(engine: InferenceEngine, tokenizer: Tokenizer,
                cfg: Optional[ServerConfig] = None, *,
                deploy=None,
                ) -> Tuple[ThreadingHTTPServer, AsyncEngine]:
    """Build (but don't start) the HTTP server; caller runs serve_forever().

    When ``cfg.gateway`` is set and enabled, an
    :class:`~dlti_tpu.serving.gateway.AdmissionGateway` is built between
    the handlers and the engine (reachable as ``httpd.gateway``); left
    unset, admission is the legacy direct path.

    ``deploy`` is an optional
    :class:`~dlti_tpu.serving.deploy.DeploymentController` (built by
    ``scripts/serve.py --deploy-watch``): it gains the ``/v1/deploy``
    surface, a ``deploy.json`` section in flight dumps, and its thread is
    started here / stopped by :func:`serve`'s shutdown path.
    """
    cfg = cfg or ServerConfig()
    async_engine = AsyncEngine(engine)
    registry = build_registry(async_engine)
    # Name this process's row in merged Perfetto exports — fleet workers
    # label themselves "worker<N>"; the front process is "supervisor"
    # when it runs a fleet (it federates worker span tails) and plain
    # "server" otherwise.
    get_tracer().process_label = (
        "supervisor" if getattr(engine, "trace", None) is not None
        else "server")
    gateway = None
    if cfg.gateway is not None and cfg.gateway.enabled:
        gateway = AdmissionGateway(async_engine, cfg.gateway, registry)

    # Self-monitoring layer (dlti_tpu.telemetry): the time-series ring is
    # always on (it is what /debug/vars and /dashboard serve — one
    # registry read per interval); watchdog and flight recorder follow
    # cfg.telemetry.
    tcfg = cfg.telemetry
    wcfg = tcfg.watchdog if tcfg is not None else None
    sampler = TimeSeriesSampler(
        interval_s=wcfg.interval_s if wcfg is not None else 1.0,
        registry=registry)
    if getattr(engine, "memledger", None) is not None \
            and engine.memledger.enabled:
        # Ledger scalars into the ring: /debug/vars + /dashboard get the
        # "where the memory lives" series, and the watchdog's
        # hbm_pressure rule reads hbm_headroom_frac from here.
        sampler.add_source(engine.memledger.scalars)
    # SLO engine (telemetry.slo): objectives over the SLIs the registry
    # already carries — lifecycle histograms (bucket-snapped latency
    # cuts), gateway admission counters (per-class availability). The
    # tracker is pull-driven: the sampler's interval pull doubles as its
    # evaluation cadence (ring series for /dashboard), the watchdog pulls
    # active_burns, /debug/slo pulls to_dict.
    slo_tracker = None
    if tcfg is not None and getattr(tcfg, "slo", None) is not None:
        from dlti_tpu.telemetry.slo import build_tracker as _build_slo

        classes = ()
        if gateway is not None:
            from dlti_tpu.serving.gateway import PRIORITIES

            classes = PRIORITIES
        slo_tracker = _build_slo(
            tcfg.slo, telemetry=engine.telemetry,
            stats_fn=registry.stats_dict if gateway is not None else None,
            classes=classes)
        if slo_tracker is not None:
            sampler.add_source(slo_tracker.scalars)
    sampler.start()
    recorder = None
    if tcfg is not None and tcfg.flight_recorder.enabled:
        import dataclasses as _dc

        fcfg = tcfg.flight_recorder
        if not get_tracer().enabled:
            # The black box needs a span tail even when no --trace-dir
            # export was requested (same rationale as the trainer's).
            from dlti_tpu.telemetry import configure_tracer

            configure_tracer(enabled=True, capacity=tcfg.trace_capacity)
        recorder = FlightRecorder(
            fcfg.dir, sampler=sampler, config=_dc.asdict(cfg),
            max_spans=fcfg.max_spans, timeseries_tail=fcfg.timeseries_tail,
            keep=fcfg.keep)
        recorder.add_metrics_source(registry.stats_dict)
        if getattr(engine, "memledger", None) is not None \
                and engine.memledger.enabled:
            recorder.add_memory_source(engine.memledger.to_dict)
        if slo_tracker is not None:
            recorder.add_slo_source(slo_tracker.to_dict)
        if deploy is not None:
            recorder.add_deploy_source(deploy.to_dict)
        recorder.note(role="serving", model=cfg.model_name)
        install_recorder(recorder)
    watchdog = None
    if wcfg is not None and wcfg.enabled:
        watchdog = AnomalyWatchdog(wcfg, sampler, slo=slo_tracker)
        if recorder is not None:
            recorder.add_context_source(
                lambda: {"watchdog_alerts": list(watchdog.alerts)})
        watchdog.start()

    if deploy is not None:
        deploy.start()

    handler = type("BoundHandler", (_Handler,), {
        "async_engine": async_engine, "tokenizer": tokenizer, "cfg": cfg,
        "registry": registry, "gateway": gateway, "sampler": sampler,
        "slo": slo_tracker, "deploy": deploy,
        "profile_lock": threading.Lock(),
    })
    httpd = ThreadingHTTPServer((cfg.host, cfg.port), handler)
    httpd.daemon_threads = True
    httpd.gateway = gateway
    httpd.sampler = sampler
    httpd.watchdog = watchdog
    httpd.flight_recorder = recorder
    httpd.slo = slo_tracker
    httpd.deploy = deploy
    return httpd, async_engine


def serve(engine: InferenceEngine, tokenizer: Tokenizer,
          cfg: Optional[ServerConfig] = None, *, deploy=None) -> None:
    """Blocking entry point (used by ``scripts/serve.py``)."""
    cfg = cfg or ServerConfig()
    httpd, async_engine = make_server(engine, tokenizer, cfg,
                                      deploy=deploy)
    from dlti_tpu.telemetry import startup

    startup.mark_startup("ready")  # the socket is bound; serve_forever next
    gateway = httpd.gateway
    get_logger().info("serving on http://%s:%d (model=%s)",
                      cfg.host, cfg.port, cfg.model_name)
    get_logger().info(
        "start-up, seconds since process start: %s; %d programs compiled "
        "in %.1f s, %d fetched from the compile cache in %.1f s",
        ", ".join(f"{p} {g.value:.1f}"
                  for p, g in startup.startup_gauges.items()),
        startup.compilations_total.value, startup.compile_seconds_total.value,
        startup.compile_cache_hits_total.value,
        startup.compile_cache_fetch_seconds_total.value)
    # SIGTERM (k8s eviction, orchestrator `kill`) gets the same clean
    # path as Ctrl-C: unblock serve_forever so the finally drains the
    # stepper and closes the socket instead of dying mid-decode. With a
    # gateway the path is a GRACEFUL DRAIN: new admissions 503, /health
    # flips to "draining" (the LB stops routing), queued + in-flight
    # requests finish (bounded by drain_grace_s), then the server exits.
    # httpd.shutdown() must run OFF the serving thread (it joins it).
    import signal as _signal

    def _graceful_stop():
        if httpd.flight_recorder is not None:
            # SIGTERM is a trigger too: the black box records what was
            # in flight when the orchestrator pulled the plug.
            httpd.flight_recorder.dump(reason="sigterm_drain", force=True)
        if gateway is not None:
            gateway.drain()
            gateway.wait_idle(gateway.cfg.drain_grace_s)
        httpd.shutdown()

    def _on_term(signum, frame):
        threading.Thread(target=_graceful_stop, daemon=True).start()

    prev_handler = None
    installed = False
    try:
        prev_handler = _signal.signal(_signal.SIGTERM, _on_term)
        installed = True
    except ValueError:
        pass  # not the main thread (embedded use): SIGTERM stays default
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        if installed:
            # Restore (trainer.py's pattern): a stale handler closing
            # over the dead httpd would otherwise swallow every later
            # SIGTERM for the process lifetime.
            _signal.signal(_signal.SIGTERM,
                           prev_handler or _signal.SIG_DFL)
        if httpd.deploy is not None:
            # Stop the delivery pipeline FIRST: a promotion racing the
            # drain would roll replicas while the stepper is parking.
            httpd.deploy.stop()
        if gateway is not None:
            gateway.shutdown()
        if httpd.watchdog is not None:
            httpd.watchdog.stop()
        httpd.sampler.stop()
        if httpd.flight_recorder is not None and \
                get_recorder() is httpd.flight_recorder:
            install_recorder(None)
        async_engine.shutdown()
        httpd.server_close()
