"""Unified telemetry layer: metrics registry + structured span tracing.

One subsystem backing both planes' observability (previously scattered
across a hand-rolled Prometheus emitter in ``serving/server.py``, the
reference-parity CSV in ``utils/metrics.py``, ``StepTimer`` in
``utils/logging.py``, and raw ``jax.profiler`` windows in ``trainer.py``):

* :mod:`~dlti_tpu.telemetry.registry` — labeled counters / gauges /
  histograms + Prometheus text exposition; the single backing store for
  the server's ``/stats`` and ``/metrics`` endpoints.
* :mod:`~dlti_tpu.telemetry.tracer` — bounded-ring host-side span tracer
  (near-zero cost when disabled) exporting Chrome-trace JSON viewable in
  Perfetto.
* :mod:`~dlti_tpu.telemetry.lifecycle` — per-request lifecycle telemetry
  for the serving engine (TTFT/TPOT/queue-time histograms + spans).
* :mod:`~dlti_tpu.telemetry.steplog` — per-step JSONL stream for training
  (superset of the reference CSV schema).
* :mod:`~dlti_tpu.telemetry.heartbeat` — multi-host per-process
  last-seen-step gauge (straggler visibility).
* :mod:`~dlti_tpu.telemetry.timeseries` — bounded in-process time-series
  ring behind ``GET /debug/vars`` and the self-contained ``/dashboard``.
* :mod:`~dlti_tpu.telemetry.watchdog` — anomaly rule engine (hung step,
  throughput collapse, queue buildup, heartbeat staleness, checkpoint
  retry storms) with log/dump/abort escalation.
* :mod:`~dlti_tpu.telemetry.flightrecorder` — black-box ``flight-*/``
  dumps (span tail + metrics + time-series tail + live context) on
  faults, rendered by ``scripts/postmortem.py``.
* :mod:`~dlti_tpu.telemetry.ledger` — goodput ledger (every training
  second booked to one bucket, conservation-tested) + per-request
  critical-path attribution (phase breakdowns summing to client-observed
  latency, ``GET /debug/slow``), stitched across elastic restarts; the
  serving stepper's always-on phase clock (``StepperAccount``: where a
  decode round's host time goes, a record of every stall) and the
  collector's pauses.
* :mod:`~dlti_tpu.telemetry.memledger` — HBM memory ledger (every
  device byte attributed to a named owner, conservation-tested against
  ``jax.live_arrays()``/``memory_stats()``), feeding ``GET
  /debug/memory``, ``memory.json`` OOM forensics, the watchdog's
  hbm_pressure rule, and the engine's headroom-aware admission.
* :mod:`~dlti_tpu.telemetry.slo` — declarative SLO engine: objectives
  over the SLIs above (latency histograms, gateway admission counters,
  goodput fraction), rolling error budgets per (objective, tenant
  class), multi-window multi-burn-rate alerting feeding the watchdog's
  slo_burn rule, ``GET /debug/slo``, and ``slo.json`` flight forensics.
"""

from dlti_tpu.telemetry.registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    LATENCY_BUCKETS,
    MetricsRegistry,
    TPOT_BUCKETS,
)
from dlti_tpu.telemetry.tracer import (  # noqa: F401
    SpanTracer,
    configure_tracer,
    get_tracer,
)
from dlti_tpu.telemetry.lifecycle import RequestTelemetry  # noqa: F401
from dlti_tpu.telemetry.steplog import (  # noqa: F401
    StepLogWriter,
    jsonl_stream_columns,
    metrics_csv_columns,
    schedule_lr,
)
from dlti_tpu.telemetry.heartbeat import Heartbeat  # noqa: F401
from dlti_tpu.telemetry.timeseries import (  # noqa: F401
    TimeSeriesSampler,
    render_dashboard_html,
)
from dlti_tpu.telemetry.watchdog import (  # noqa: F401
    AnomalyWatchdog,
    WATCHDOG_METRIC_NAMES,
)
from dlti_tpu.telemetry.flightrecorder import (  # noqa: F401
    FLIGHT_METRIC_NAMES,
    FlightRecorder,
    get_recorder,
    install as install_recorder,
)
from dlti_tpu.telemetry.ledger import (  # noqa: F401
    CriticalPathTracker,
    GC_METRIC_NAMES,
    GOODPUT_BUCKETS,
    GoodputLedger,
    LEDGER_METRIC_NAMES,
    NullStepperAccount,
    REQUEST_PHASE_METRIC_NAMES,
    REQUEST_PHASES,
    STEPPER_METRIC_NAMES,
    StepperAccount,
    install_gc_hook,
    remove_gc_hook,
    request_breakdown,
    stitch_ledgers,
)
from dlti_tpu.telemetry.slo import (  # noqa: F401
    Objective,
    SLO_METRIC_NAMES,
    SLOTracker,
    availability_objective,
    build_tracker as build_slo_tracker,
    goodput_objective,
    histogram_objective,
    parse_burn_tiers,
    standard_objectives,
)
from dlti_tpu.telemetry.memledger import (  # noqa: F401
    MEMLEDGER_METRIC_NAMES,
    MEMORY_OWNERS,
    MemoryBalloon,
    MemoryLedger,
    executable_memory_analysis,
    is_oom_error,
    tree_nbytes,
)
