"""Static guard: every metric the package registers follows the
``dlti_`` prefix + snake_case convention.

The /metrics names are a scrape contract (test_bench_contract pins the
known sets); this guard closes the gap for *new* names — a metric added
anywhere in the package that breaks the convention fails here before it
can silently break external dashboards. It walks a fully-assembled
serving registry (engine stats + lifecycle histograms + gateway +
heartbeat + watchdog/flight counters + the trace eviction counter) after
importing the trainer and server modules, plus every module-level metric
object the training side owns (checkpoint store, prefetch, watchdog,
flight recorder, elastic supervisor).
"""

import re

import pytest

# Importing these modules materializes every module-level metric object
# in the package (checkpoint store counters, watchdog/flight counters).
import dlti_tpu.serving.server as server_mod
import dlti_tpu.training.trainer  # noqa: F401

NAME_RE = re.compile(r"^dlti_[a-z0-9]+(_[a-z0-9]+)*$")


def _assert_convention(names, where):
    bad = [n for n in names if not NAME_RE.fullmatch(n)]
    assert not bad, (
        f"metric names breaking the dlti_ + snake_case convention in "
        f"{where}: {bad} — the /metrics exposition is a scrape contract; "
        f"rename before shipping")


def test_pinned_name_tuples_follow_convention():
    from dlti_tpu.checkpoint import CKPT_METRIC_NAMES
    from dlti_tpu.data.prefetch import PREFETCH_METRIC_NAMES
    from dlti_tpu.serving.adapters import ADAPTER_METRIC_NAMES
    from dlti_tpu.serving.deploy import DEPLOY_METRIC_NAMES
    from dlti_tpu.serving.disagg import (
        KV_HANDOFF_METRIC_NAMES, POOL_METRIC_NAMES,
    )
    from dlti_tpu.serving.engine import SPEC_METRIC_NAMES
    from dlti_tpu.serving.fleet import FLEET_METRIC_NAMES
    from dlti_tpu.serving.gateway import GATEWAY_METRIC_NAMES
    from dlti_tpu.serving.lifecycle import LIFECYCLE_METRIC_NAMES
    from dlti_tpu.serving.prefix_cache import PREFIX_CACHE_METRIC_NAMES
    from dlti_tpu.serving.wire import WIRE_METRIC_NAMES
    from dlti_tpu.telemetry import (
        FLIGHT_METRIC_NAMES, LEDGER_METRIC_NAMES,
        REQUEST_PHASE_METRIC_NAMES, SLO_METRIC_NAMES,
        WATCHDOG_METRIC_NAMES,
    )
    from dlti_tpu.telemetry.distributed_trace import TRACE_METRIC_NAMES
    from dlti_tpu.telemetry.heartbeat import HEARTBEAT_METRIC_NAMES
    from dlti_tpu.telemetry.memledger import MEMLEDGER_METRIC_NAMES
    from dlti_tpu.training.elastic import ELASTIC_METRIC_NAMES
    from dlti_tpu.training.sentinel import (
        SDC_METRIC_NAMES, SENTINEL_METRIC_NAMES,
    )
    from dlti_tpu.utils.durable_io import DISK_METRIC_NAMES

    for tup, where in ((CKPT_METRIC_NAMES, "checkpoint"),
                       (DISK_METRIC_NAMES, "durable_io"),
                       (PREFETCH_METRIC_NAMES, "prefetch"),
                       (GATEWAY_METRIC_NAMES, "gateway"),
                       (PREFIX_CACHE_METRIC_NAMES, "prefix_cache"),
                       (WATCHDOG_METRIC_NAMES, "watchdog"),
                       (FLIGHT_METRIC_NAMES, "flightrecorder"),
                       (ELASTIC_METRIC_NAMES, "elastic"),
                       (SENTINEL_METRIC_NAMES, "sentinel"),
                       (SDC_METRIC_NAMES, "sdc"),
                       (LEDGER_METRIC_NAMES, "ledger"),
                       (REQUEST_PHASE_METRIC_NAMES, "request_phase"),
                       (MEMLEDGER_METRIC_NAMES, "memledger"),
                       (SLO_METRIC_NAMES, "slo"),
                       (HEARTBEAT_METRIC_NAMES, "heartbeat"),
                       (POOL_METRIC_NAMES, "disagg-pools"),
                       (KV_HANDOFF_METRIC_NAMES, "kv-handoff"),
                       (ADAPTER_METRIC_NAMES, "adapters"),
                       (DEPLOY_METRIC_NAMES, "deploy"),
                       (LIFECYCLE_METRIC_NAMES, "lifecycle"),
                       (WIRE_METRIC_NAMES, "wire"),
                       (FLEET_METRIC_NAMES, "fleet"),
                       (SPEC_METRIC_NAMES, "spec-decode"),
                       (TRACE_METRIC_NAMES, "distributed-trace")):
        _assert_convention(tup, where)


def test_module_level_metric_objects_follow_convention():
    from dlti_tpu.checkpoint import store
    from dlti_tpu.serving import adapters, deploy, fleet, lifecycle, wire
    from dlti_tpu.telemetry import (
        distributed_trace, flightrecorder, ledger, memledger, slo, watchdog,
    )
    from dlti_tpu.training import elastic, sentinel
    from dlti_tpu.utils import durable_io

    objs = (lifecycle.quarantines_total, lifecycle.reinstates_total,
            lifecycle.flaps_total, lifecycle.migrations_total,
            lifecycle.migration_fallbacks_total,
            lifecycle.replica_state_gauge,
            wire.frames_total, wire.wire_bytes_total,
            fleet.workers_alive_gauge, fleet.respawns_total,
            adapters.loads_total, adapters.evictions_total,
            adapters.pool_hits_total, adapters.pool_misses_total,
            adapters.pool_slots_gauge, adapters.pool_bytes_gauge,
            deploy.candidates_total, deploy.canaries_total,
            deploy.promotions_total, deploy.rollbacks_total,
            deploy.rejected_total, deploy.incumbent_step_gauge,
            store.save_seconds, store.restore_seconds, store.corrupt_skipped,
            store.save_retries, store.last_verified_step,
            watchdog.alerts_total, flightrecorder.dumps_total,
            distributed_trace.federated_spans_total,
            distributed_trace.unparented_spans_total,
            distributed_trace.clock_offset_gauge,
            elastic.restarts_total, elastic.generation_gauge,
            elastic.world_size_gauge,
            sentinel.anomalies_total, sentinel.skipped_updates_total,
            sentinel.rollbacks_total, sentinel.quarantined_windows_total,
            sentinel.sdc_probes_total, sentinel.sdc_mismatches_total,
            ledger.goodput_fraction_gauge, ledger.goodput_seconds_total,
            ledger.goodput_mfu_gauge, ledger.phase_seconds_total,
            ledger.phase_requests_total,
            memledger.hbm_bytes_gauge, memledger.hbm_peak_gauge,
            memledger.hbm_headroom_gauge, memledger.hbm_untracked_gauge,
            slo.compliance_gauge, slo.budget_remaining_gauge,
            slo.burn_rate_gauge,
            durable_io.free_bytes_gauge, durable_io.write_errors_total,
            durable_io.degraded_gauge)
    _assert_convention([m.name for m in objs], "module-level metrics")


@pytest.fixture()
def full_registry():
    """A registry assembled the way a real gateway'd server assembles it,
    without paying for a real engine: a stats-shaped fake behind
    build_registry, then the gateway's counters and scalar source, the
    heartbeat gauge, and the prefetcher's metrics registered on top."""
    from dlti_tpu.config import GatewayConfig
    from dlti_tpu.serving.gateway import AdmissionGateway
    from dlti_tpu.telemetry import Heartbeat, RequestTelemetry, SpanTracer

    class FakeEngine:
        stats = {"requests": 0, "generated_tokens": 0, "prefill_tokens": 0,
                 "preemptions": 0, "decode_steps": 0, "decode_slot_steps": 0,
                 "prefix_cached_tokens": 0, "spec_proposed": 0,
                 "spec_accepted": 0, "spec_paused_rounds": 0,
                 "decode_host_uploads": 0, "decode_program_calls": 0}
        telemetry = RequestTelemetry(tracer=SpanTracer(enabled=False))
        waiting: list = []
        num_active = 0
        num_free_blocks = 0

        class cfg:
            max_seqs = 4

    class FakeAsync:
        engine = FakeEngine()

    registry = server_mod.build_registry(FakeAsync())
    gw = AdmissionGateway(FakeAsync(), GatewayConfig(enabled=True), registry)
    try:
        Heartbeat(registry=registry)
        from dlti_tpu.data.prefetch import PREFETCH_METRIC_NAMES

        for name in PREFETCH_METRIC_NAMES:
            registry.gauge(name) if name.endswith("depth") \
                else registry.histogram(name)
        yield registry
    finally:
        gw.shutdown()


def test_every_registered_metric_follows_convention(full_registry):
    names = full_registry.metric_names()
    # The walk actually covered the full surface (engine scalars, request
    # histograms, gateway, heartbeat, watchdog/flight, trace eviction) —
    # an empty or partial registry would vacuously pass.
    for expected in ("dlti_requests", "dlti_request_ttft_seconds",
                     "dlti_gateway_queue_depth",
                     "dlti_gateway_admitted_total",
                     "dlti_heartbeat_last_step",
                     "dlti_watchdog_alerts_total",
                     "dlti_flight_dumps_total",
                     "dlti_trace_dropped_events",
                     "dlti_trace_federated_spans_total",
                     "dlti_trace_unparented_spans_total",
                     "dlti_trace_clock_offset_seconds",
                     "dlti_train_prefetch_queue_depth",
                     "dlti_prefix_cache_hits_total",
                     "dlti_prefix_cache_blocks",
                     "dlti_prefix_cache_hit_rate",
                     "dlti_adapter_loads_total",
                     "dlti_adapter_pool_hits_total",
                     "dlti_adapter_pool_bytes",
                     "dlti_sentinel_rollbacks_total",
                     "dlti_sdc_mismatches_total",
                     "dlti_goodput_fraction",
                     "dlti_goodput_seconds_total",
                     "dlti_request_phase_seconds_total",
                     "dlti_hbm_bytes",
                     "dlti_hbm_headroom_bytes",
                     "dlti_slo_compliance",
                     "dlti_slo_error_budget_remaining",
                     "dlti_slo_burn_rate",
                     "dlti_disk_free_bytes",
                     "dlti_disk_write_errors_total",
                     "dlti_disk_degraded",
                     "dlti_replica_lifecycle_quarantines_total",
                     "dlti_replica_state",
                     "dlti_deploy_rollbacks_total",
                     "dlti_deploy_incumbent_step",
                     "dlti_spec_proposed_total",
                     "dlti_spec_acceptance_rate",
                     "dlti_spec_draft_len",
                     "dlti_heartbeat_lag_steps"):
        assert expected in names, f"walk missed {expected}: {names}"
    _assert_convention(names, "assembled serving registry")


def test_convention_guard_actually_rejects():
    """The regex does its job: names the convention forbids fail it."""
    for bad in ("requests", "dlti_CamelCase", "dlti_", "dlti__double",
                "dlti_trailing_", "vllm_requests", "dlti_has-dash"):
        assert not NAME_RE.fullmatch(bad), bad
    for good in ("dlti_requests", "dlti_gateway_queue_depth",
                 "dlti_request_ttft_seconds", "dlti_ckpt_last_verified_step"):
        assert NAME_RE.fullmatch(good), good
