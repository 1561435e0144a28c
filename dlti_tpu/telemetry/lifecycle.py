"""Request-lifecycle telemetry for the serving engine.

vLLM treats request-lifecycle metrics (TTFT/TPOT, queue time, preemptions)
as a first-class engine surface; this is that surface for
:class:`~dlti_tpu.serving.engine.InferenceEngine`. One instance per engine
(or shared across replicas — histograms aggregate naturally) holds the
latency histograms and converts the timestamps the engine already keeps on
each :class:`Request` into Chrome-trace spans:

    submitted → admitted        ``request/queued``   (queue-time histogram)
    admitted  → first token     ``request/prefill``  (TTFT histogram, from
                                                      arrival)
    first tok → finished        ``request/decode``   (TPOT histogram)

When the admission gateway fronts the engine, its own phase precedes
these on the same timeline under the ``gateway`` category:
``gateway/enqueued`` (accepted), ``gateway/queued`` (admission wait,
complete-span), and the ``gateway/rejected`` / ``gateway/shed`` instants
for refusals and queued-deadline sheds (``serving.gateway``).

Spans are emitted *after the fact* from recorded timestamps
(:meth:`SpanTracer.complete`), so the engine's hot path only ever touches
monotonic-clock floats it already records. Each request's spans share a
``tid`` derived from its id, giving one Perfetto track per request.
"""

from __future__ import annotations

import time
import zlib
from typing import Optional

from dlti_tpu.telemetry.ledger import (
    CriticalPathTracker, StepperAccount, note_readmitted, note_requeue,
)
from dlti_tpu.telemetry.registry import (
    Histogram, LATENCY_BUCKETS, TPOT_BUCKETS,
)
from dlti_tpu.telemetry.tracer import SpanTracer, get_tracer


def _req_tid(request_id: str) -> int:
    # Stable per-request Perfetto track in a compact id range, offset past
    # plausible real thread ids' low bits colliding at 0.
    return 1_000_000 + (zlib.crc32(request_id.encode()) % 1_000_000)


class RequestTelemetry:
    """Histograms + lifecycle span emission for engine requests."""

    def __init__(self, tracer: Optional[SpanTracer] = None,
                 slow_k: int = 32):
        self.tracer = tracer if tracer is not None else get_tracer()
        # Critical-path attribution (telemetry.ledger): every finished
        # request's phase breakdown feeds dlti_request_phase_* and the
        # GET /debug/slow worst-K retention. Shared across replicas like
        # the histograms, so the fleet attributes into one place.
        self.critical_path = CriticalPathTracker(slow_k=slow_k)
        self.ttft = Histogram(
            "dlti_request_ttft_seconds", LATENCY_BUCKETS,
            help="time from request arrival to first generated token",
            stats_key="request_ttft_seconds")
        self.tpot = Histogram(
            "dlti_request_tpot_seconds", TPOT_BUCKETS,
            help="mean per-output-token latency after the first token",
            stats_key="request_tpot_seconds")
        self.queue_time = Histogram(
            "dlti_request_queue_time_seconds", LATENCY_BUCKETS,
            help="time from request arrival to slot admission",
            stats_key="request_queue_time_seconds")
        # The phase clock of the thread that steps the engine(s) this
        # telemetry serves (telemetry.ledger): the engine's step phases
        # and the server's loop round them book into one account, always.
        self.stepper = StepperAccount(self.tracer)

    def histograms(self):
        return (self.ttft, self.tpot, self.queue_time)

    # -- lifecycle hooks (called by the engine) -------------------------
    # Requests flagged ``shadow`` (the deployment controller's mirrored
    # canary traffic, serving.deploy) never book into the client-facing
    # histograms or phase attribution: shadow results never reach a
    # client, so counting them would dilute the SLIs the SLO objectives
    # are computed from.
    def on_submitted(self, req) -> None:
        if getattr(req, "shadow", False):
            return
        self.tracer.instant("request/submitted", cat="request",
                            tid=_req_tid(req.request_id), id=req.request_id,
                            trace=getattr(req, "trace_id", ""))

    def on_admitted(self, req) -> None:
        """First admission observes queue time; a re-admission after
        preemption keeps the original queue-time sample (the request
        queued once — recompute is decode-side churn) and only marks the
        trace."""
        if getattr(req, "shadow", False):
            return
        now = time.monotonic()
        # Close any open requeue mark (preemption / failover wait books
        # to its own phase in the request's critical-path breakdown).
        note_readmitted(req)
        if req.admitted_time is None:
            req.admitted_time = now
            self.queue_time.observe(now - req.arrival_time)
            self.tracer.complete(
                "request/queued", req.arrival_time, now, cat="request",
                tid=_req_tid(req.request_id), id=req.request_id,
                trace=getattr(req, "trace_id", ""))
        else:
            self.tracer.instant("request/readmitted", cat="request",
                                tid=_req_tid(req.request_id),
                                id=req.request_id,
                                preemptions=req.num_preemptions)

    def on_first_token(self, req) -> None:
        if getattr(req, "shadow", False):
            return
        self.ttft.observe(req.first_token_time - req.arrival_time)
        start = (req.admitted_time if req.admitted_time is not None
                 else req.arrival_time)
        self.tracer.complete(
            "request/prefill", start, req.first_token_time, cat="request",
            tid=_req_tid(req.request_id), id=req.request_id,
            trace=getattr(req, "trace_id", ""),
            prompt_tokens=len(req.prompt_token_ids))

    def on_finished(self, req) -> None:
        if getattr(req, "shadow", False):
            return
        n_out = len(req.output_token_ids)
        first = req.first_token_time
        finish = req.finish_time if req.finish_time is not None \
            else time.monotonic()
        if first is not None and n_out > 1:
            self.tpot.observe((finish - first) / (n_out - 1))
        self.tracer.complete(
            "request/decode",
            first if first is not None else req.arrival_time, finish,
            cat="request", tid=_req_tid(req.request_id), id=req.request_id,
            trace=getattr(req, "trace_id", ""),
            output_tokens=n_out, finish_reason=req.finish_reason,
            preemptions=req.num_preemptions)
        # Phase attribution last: the breakdown reads the timestamps the
        # spans above were emitted from (per request, never per token).
        self.critical_path.observe(req)

    def on_preempted(self, req) -> None:
        note_requeue(req, "preempt")
        self.tracer.instant("request/preempted", cat="request",
                            tid=_req_tid(req.request_id), id=req.request_id)
