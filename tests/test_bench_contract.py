"""bench.py driver contract, plus the metric-name scrape contracts.

The driver parses bench.py's LAST stdout line as JSON and records the
exit code. bench.py runs one configuration: whatever happens — no
backend, a bad setting, a CPU where a chip was expected — there is
exactly ONE JSON line, and the exit code is 0 only when that line holds a
measurement taken on an accelerator.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run(env_extra, timeout=120):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(env_extra)
    proc = subprocess.run([sys.executable, BENCH], env=env,
                          capture_output=True, text=True, timeout=timeout,
                          cwd=REPO)
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    return proc, lines


def _error_line(proc, lines):
    assert len(lines) == 1, lines
    out = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "error"):
        assert key in out, out
    assert out["value"] == 0.0
    return out


def test_unreachable_backend_fails_with_json():
    """Backend init failure -> one error JSON line + nonzero exit, at
    once (no probe child, no retry loop, no deadline thread)."""
    proc, lines = _run({"JAX_PLATFORMS": "bogus"}, timeout=60)
    assert proc.returncode == 1, proc.stderr[-500:]
    assert "bogus" in _error_line(proc, lines)["error"]


def test_bad_env_config_emits_json():
    """A setting typo exits 2 with the JSON line, before any backend or
    model work."""
    proc, lines = _run({"JAX_PLATFORMS": "cpu", "BENCH_QUANT": "int4"})
    assert proc.returncode == 2, proc.stderr[-500:]
    assert "BENCH_QUANT" in _error_line(proc, lines)["error"]


def test_unknown_model_or_depth_emits_json():
    """There is one configuration and no ladder of fallbacks: a model that
    does not resolve, or a depth cut it cannot take, is a failure (exit 2),
    not a reason to try another model."""
    proc, lines = _run({"JAX_PLATFORMS": "cpu", "BENCH_MODEL": "mistral_9b"})
    assert proc.returncode == 2, proc.stderr[-500:]
    assert "unknown model" in _error_line(proc, lines)["error"]
    proc, lines = _run({"JAX_PLATFORMS": "cpu", "BENCH_MODEL": "mistral_7b",
                        "DLTI_MODEL_LAYERS": "33"})
    assert proc.returncode == 2, proc.stderr[-500:]
    assert "whole layers" in _error_line(proc, lines)["error"]


def test_cpu_backend_is_a_failure_not_a_fallback():
    """No chip -> the run fails; it never reports a CPU timing under a
    device metric's name."""
    proc, lines = _run({"JAX_PLATFORMS": "cpu", "BENCH_MODEL": "llama_tiny",
                        "BENCH_BS": "2", "BENCH_SEQ": "64",
                        "BENCH_STEPS": "2"})
    assert proc.returncode == 1, proc.stderr[-500:]
    out = _error_line(proc, lines)
    assert "no accelerator" in out["error"] and "cpu" in out["error"]
    assert "device_kind" not in out


def test_gateway_metric_names_are_schema_stable():
    """The dlti_gateway_* exposition names are a scrape contract like the
    legacy dlti_<stat> names: renaming one silently breaks external
    dashboards, so the full set is pinned here."""
    from dlti_tpu.serving.gateway import GATEWAY_METRIC_NAMES

    assert GATEWAY_METRIC_NAMES == (
        "dlti_gateway_queue_depth",
        "dlti_gateway_queued_tokens",
        "dlti_gateway_inflight",
        "dlti_gateway_replicas_alive",
        "dlti_gateway_admitted_total",
        "dlti_gateway_rejected_total",
        "dlti_gateway_shed_total",
        "dlti_gateway_retries_total",
        "dlti_gateway_replica_faults_total",
        "dlti_gateway_affinity_sticky_total",
        "dlti_gateway_affinity_spill_total",
    )


def test_prefix_cache_metric_names_are_schema_stable():
    """Tiered prefix-cache telemetry names are a scrape contract like the
    gateway set: per-tier (tier="hbm" | "host" | "disk") hit / miss /
    eviction / promotion / demotion counters plus the per-tier block
    gauge, all registered by the server registry."""
    from dlti_tpu.serving import prefix_cache as pc

    assert pc.PREFIX_CACHE_METRIC_NAMES == (
        "dlti_prefix_cache_hits_total",
        "dlti_prefix_cache_misses_total",
        "dlti_prefix_cache_evictions_total",
        "dlti_prefix_cache_promotions_total",
        "dlti_prefix_cache_demotions_total",
        "dlti_prefix_cache_blocks",
    )
    assert pc.hits_total.name == pc.PREFIX_CACHE_METRIC_NAMES[0]
    assert pc.misses_total.name == pc.PREFIX_CACHE_METRIC_NAMES[1]
    assert pc.evictions_total.name == pc.PREFIX_CACHE_METRIC_NAMES[2]
    assert pc.promotions_total.name == pc.PREFIX_CACHE_METRIC_NAMES[3]
    assert pc.demotions_total.name == pc.PREFIX_CACHE_METRIC_NAMES[4]
    assert pc.blocks_gauge.name == pc.PREFIX_CACHE_METRIC_NAMES[5]


def test_host_overlap_metric_names_are_schema_stable():
    """Host-latency-hiding telemetry names are a scrape contract like the
    gateway set: the training prefetcher's gauge/histogram, the engine's
    decode host-prep histogram, and the counters of what a decode round
    costs the host (exposed via the engine stats scalar source as
    dlti_<key>)."""
    from dlti_tpu.data.prefetch import PREFETCH_METRIC_NAMES

    assert PREFETCH_METRIC_NAMES == (
        "dlti_train_prefetch_queue_depth",
        "dlti_train_prefetch_stall_seconds",
    )

    from dlti_tpu.telemetry import RequestTelemetry

    tel = RequestTelemetry()
    assert [h.name for h in tel.histograms()] == [
        "dlti_request_ttft_seconds",
        "dlti_request_tpot_seconds",
        "dlti_request_queue_time_seconds",
    ]
    # The decode round's host path (its prep among nine phases) is the
    # stepper's phase clock: seconds and entries by phase, the thread's
    # CPU, every stall, the collector's pauses, the handlers' CPU.
    from dlti_tpu.serving import server
    from dlti_tpu.telemetry import ledger

    assert ledger.STEPPER_METRIC_NAMES == (
        "dlti_stepper_phase_seconds_total",
        "dlti_stepper_phase_entries_total",
        "dlti_stepper_cpu_seconds_total",
        "dlti_stepper_device_wait_cpu_seconds_total",
        "dlti_stepper_marked_host_seconds_total",
        "dlti_stepper_marked_decode_steps_total",
        "dlti_stepper_stalls_total",
        "dlti_stepper_stall_seconds_total",
    )
    assert [m.name for m in tel.stepper.metrics()] == list(
        ledger.STEPPER_METRIC_NAMES)
    assert ledger.GC_METRIC_NAMES == (
        "dlti_gc_pause_seconds_total", "dlti_gc_collections_total")
    assert (ledger.gc_pause_seconds_total.name,
            ledger.gc_collections_total.name) == ledger.GC_METRIC_NAMES
    assert (server.sse_handler_cpu_seconds_total.name,
            server.sse_events_total.name) == (
        "dlti_sse_handler_cpu_seconds_total", "dlti_sse_events_total")

    # Engine stats keys ride the /metrics scalar source (dlti_ prefix):
    # dlti_decode_host_uploads / dlti_decode_program_calls, which the
    # executor books into the dict the engine hands it. The layout of the
    # packed round is a contract of the decode programs' first lines.
    from dlti_tpu.serving.decode_state import RoundPacking
    from dlti_tpu.serving.engine import InferenceEngine

    import inspect

    src = inspect.getsource(InferenceEngine.__init__)
    assert '"decode_host_uploads": 0, "decode_program_calls": 0' in src
    assert "decode_state_" not in src
    pk = RoundPacking(2, 5, "adapter_ids")
    assert list(pk.columns) == [
        "input_ids", "positions", "block_tables", "slot_keys", "gen_counts",
        "temperature", "top_k", "top_p", "adapter_ids"]
    assert pk.width == 5 + 9 and RoundPacking(2, 5).width == 5 + 8


def test_ckpt_metric_names_are_schema_stable():
    """Checkpoint-robustness telemetry names are a scrape contract like
    the gateway and prefetch sets: save/restore duration histograms, the
    corrupt-quarantine and save-retry counters, and the
    last-verified-step gauge."""
    from dlti_tpu.checkpoint import CKPT_METRIC_NAMES
    from dlti_tpu.checkpoint import store

    assert CKPT_METRIC_NAMES == (
        "dlti_ckpt_save_seconds",
        "dlti_ckpt_restore_seconds",
        "dlti_ckpt_corrupt_skipped",
        "dlti_ckpt_save_retries",
        "dlti_ckpt_last_verified_step",
    )
    assert store.save_seconds.name == CKPT_METRIC_NAMES[0]
    assert store.restore_seconds.name == CKPT_METRIC_NAMES[1]
    assert store.corrupt_skipped.name == CKPT_METRIC_NAMES[2]
    assert store.save_retries.name == CKPT_METRIC_NAMES[3]
    assert store.last_verified_step.name == CKPT_METRIC_NAMES[4]


def test_watchdog_and_flight_metric_names_are_schema_stable():
    """Self-monitoring telemetry names are a scrape contract like the
    gateway/prefetch/ckpt sets: the watchdog's per-rule alert counter,
    the flight recorder's dump counter, and the tracer's ring-eviction
    counter exposed by the server registry."""
    from dlti_tpu.telemetry import FLIGHT_METRIC_NAMES, WATCHDOG_METRIC_NAMES
    from dlti_tpu.telemetry import flightrecorder, watchdog

    assert WATCHDOG_METRIC_NAMES == ("dlti_watchdog_alerts_total",)
    assert FLIGHT_METRIC_NAMES == ("dlti_flight_dumps_total",)
    assert watchdog.alerts_total.name == WATCHDOG_METRIC_NAMES[0]
    assert flightrecorder.dumps_total.name == FLIGHT_METRIC_NAMES[0]
    # The watchdog rule set is part of the alert-counter label contract
    # (dashboards filter by rule=...).
    assert watchdog.RULES == (
        "hung_step", "throughput_collapse", "queue_buildup",
        "shed_buildup", "heartbeat_stale", "ckpt_retry_storm",
        "nonfinite_step", "loss_spike", "sdc_mismatch",
        "goodput_collapse", "hbm_pressure", "disk_pressure",
        "replica_flap", "slo_burn", "canary_regression",
    )


def test_slo_metric_names_are_schema_stable():
    """SLO gauge names are a scrape contract like the watchdog/gateway
    sets: compliance, error-budget-remaining, and windowed burn rate,
    all (objective, class)-labeled and registered by the server
    registry."""
    from dlti_tpu.telemetry import SLO_METRIC_NAMES
    from dlti_tpu.telemetry import slo

    assert SLO_METRIC_NAMES == (
        "dlti_slo_compliance",
        "dlti_slo_error_budget_remaining",
        "dlti_slo_burn_rate",
    )
    assert slo.compliance_gauge.name == SLO_METRIC_NAMES[0]
    assert slo.budget_remaining_gauge.name == SLO_METRIC_NAMES[1]
    assert slo.burn_rate_gauge.name == SLO_METRIC_NAMES[2]
    # The default burn tiers are the SRE fast/slow pairing dashboards
    # and runbooks key on; changing them re-tunes every deployment.
    assert slo.DEFAULT_BURN_TIERS == "14:60:5,6:300:30"
    assert slo.parse_burn_tiers(slo.DEFAULT_BURN_TIERS) == (
        (14.0, 60.0, 5.0), (6.0, 300.0, 30.0))


def test_disk_metric_names_are_schema_stable():
    """Durable-writer health names are a scrape contract like the
    watchdog/ckpt sets: the free-bytes gauge plus the path_class-labeled
    write-error counter and degraded gauge, all registered by the server
    registry and watched by the disk_pressure rule."""
    from dlti_tpu.utils import durable_io

    assert durable_io.DISK_METRIC_NAMES == (
        "dlti_disk_free_bytes",
        "dlti_disk_write_errors_total",
        "dlti_disk_degraded",
    )
    assert durable_io.free_bytes_gauge.name == \
        durable_io.DISK_METRIC_NAMES[0]
    assert durable_io.write_errors_total.name == \
        durable_io.DISK_METRIC_NAMES[1]
    assert durable_io.degraded_gauge.name == durable_io.DISK_METRIC_NAMES[2]
    # The path-class set is the degradation-policy contract (the README
    # criticality table and the AST guard's covered modules key on it).
    assert durable_io.PATH_CLASSES == (
        "checkpoint", "adapter", "prefix_tier", "flight", "fleet_runtime",
        "steplog", "elastic", "sentinel", "watchdog",
    )


def test_lifecycle_metric_names_are_schema_stable():
    """Replica-lifecycle telemetry names are a scrape contract like the
    watchdog/disk sets: the self-healing counters (quarantine, reinstate,
    flap eviction, live migration + fallback) and the per-replica state
    gauge, all registered by the server registry and watched by the
    replica_flap rule."""
    from dlti_tpu.serving import lifecycle

    assert lifecycle.LIFECYCLE_METRIC_NAMES == (
        "dlti_replica_lifecycle_quarantines_total",
        "dlti_replica_lifecycle_reinstates_total",
        "dlti_replica_lifecycle_flaps_total",
        "dlti_replica_lifecycle_migrations_total",
        "dlti_replica_lifecycle_migration_fallbacks_total",
        "dlti_replica_state",
    )
    assert lifecycle.quarantines_total.name == \
        lifecycle.LIFECYCLE_METRIC_NAMES[0]
    assert lifecycle.reinstates_total.name == \
        lifecycle.LIFECYCLE_METRIC_NAMES[1]
    assert lifecycle.flaps_total.name == lifecycle.LIFECYCLE_METRIC_NAMES[2]
    assert lifecycle.migrations_total.name == \
        lifecycle.LIFECYCLE_METRIC_NAMES[3]
    assert lifecycle.migration_fallbacks_total.name == \
        lifecycle.LIFECYCLE_METRIC_NAMES[4]
    assert lifecycle.replica_state_gauge.name == \
        lifecycle.LIFECYCLE_METRIC_NAMES[5]
    # The state set is the replica_state gauge's value contract
    # (dashboards map code -> label via STATES order).
    assert lifecycle.STATES == (
        "live", "quarantined", "probing", "draining", "evicted",
    )


def test_deploy_metric_names_are_schema_stable():
    """Continuous-delivery telemetry names are a scrape contract like
    the lifecycle/watchdog sets: the candidate/canary/promote/rollback/
    refuse counters the canary_regression rule and release dashboards
    key on, plus the incumbent-step gauge, all registered by the server
    registry."""
    from dlti_tpu.serving import deploy

    assert deploy.DEPLOY_METRIC_NAMES == (
        "dlti_deploy_candidates_total",
        "dlti_deploy_canaries_total",
        "dlti_deploy_promotions_total",
        "dlti_deploy_rollbacks_total",
        "dlti_deploy_rejected_total",
        "dlti_deploy_incumbent_step",
    )
    assert deploy.candidates_total.name == deploy.DEPLOY_METRIC_NAMES[0]
    assert deploy.canaries_total.name == deploy.DEPLOY_METRIC_NAMES[1]
    assert deploy.promotions_total.name == deploy.DEPLOY_METRIC_NAMES[2]
    assert deploy.rollbacks_total.name == deploy.DEPLOY_METRIC_NAMES[3]
    assert deploy.rejected_total.name == deploy.DEPLOY_METRIC_NAMES[4]
    assert deploy.incumbent_step_gauge.name == \
        deploy.DEPLOY_METRIC_NAMES[5]


def test_fleet_metric_names_are_schema_stable():
    """Multi-process fleet telemetry names are a scrape contract: the
    wire-layer frame/byte counters (labeled by frame kind) and the
    supervisor's live-worker gauge + respawn counter, all federated into
    the serving registry and cross-checked by loadgen's federation
    report."""
    from dlti_tpu.serving import fleet, wire

    assert wire.WIRE_METRIC_NAMES == (
        "dlti_fleet_frames_total",
        "dlti_fleet_wire_bytes_total",
    )
    assert wire.frames_total.name == wire.WIRE_METRIC_NAMES[0]
    assert wire.wire_bytes_total.name == wire.WIRE_METRIC_NAMES[1]

    assert fleet.FLEET_METRIC_NAMES == (
        "dlti_fleet_workers_alive",
        "dlti_fleet_respawns_total",
    )
    assert fleet.workers_alive_gauge.name == fleet.FLEET_METRIC_NAMES[0]
    assert fleet.respawns_total.name == fleet.FLEET_METRIC_NAMES[1]
    # The per-worker key sets are the federation contract: counter keys
    # must sum across workers to the fleet-level dlti_{key} totals
    # (loadgen's federation report asserts this at scrape time).
    assert fleet.WORKER_COUNTER_KEYS == (
        "requests", "generated_tokens", "prefill_tokens",
        "preemptions", "decode_steps",
    )
    assert fleet.WORKER_GAUGE_KEYS == (
        "up", "active", "waiting", "free_blocks",
    )


def test_trace_metric_names_are_schema_stable():
    """Distributed-tracing federation names are a scrape contract:
    spans adopted from fleet workers, spans arriving without request or
    trace parentage, and the per-worker clock-offset gauge the rebasing
    used — registered unconditionally by build_registry so the series
    exist (at zero) even on single-process engines."""
    from dlti_tpu.telemetry import distributed_trace as dt

    assert dt.TRACE_METRIC_NAMES == (
        "dlti_trace_federated_spans_total",
        "dlti_trace_unparented_spans_total",
        "dlti_trace_clock_offset_seconds",
    )
    assert dt.federated_spans_total.name == dt.TRACE_METRIC_NAMES[0]
    assert dt.unparented_spans_total.name == dt.TRACE_METRIC_NAMES[1]
    assert dt.clock_offset_gauge.name == dt.TRACE_METRIC_NAMES[2]


def test_spec_metric_names_are_schema_stable():
    """Speculative-decode telemetry names are a scrape contract: raw
    draft-economics counters (proposed/accepted draft tokens, paused
    slot-rounds) plus the derived acceptance-rate and adaptive
    draft-length gauges, registered by build_registry's spec scalar
    source and scraped into LoadReport.spec by loadgen."""
    from dlti_tpu.serving.engine import SPEC_METRIC_NAMES

    assert SPEC_METRIC_NAMES == (
        "dlti_spec_proposed_total",
        "dlti_spec_accepted_total",
        "dlti_spec_paused_rounds_total",
        "dlti_spec_acceptance_rate",
        "dlti_spec_draft_len",
    )


def test_sentinel_metric_names_are_schema_stable():
    """Numeric-fault-sentinel telemetry names are a scrape contract like
    the watchdog/ckpt sets: anomaly/skip/rollback/quarantine counters and
    the cross-rank SDC probe counters, all registered by the server
    registry for /dashboard."""
    from dlti_tpu.training import sentinel

    assert sentinel.SENTINEL_METRIC_NAMES == (
        "dlti_sentinel_anomalies_total",
        "dlti_sentinel_skipped_updates_total",
        "dlti_sentinel_rollbacks_total",
        "dlti_sentinel_quarantined_windows_total",
    )
    assert sentinel.SDC_METRIC_NAMES == (
        "dlti_sdc_probes_total",
        "dlti_sdc_mismatches_total",
    )
    assert sentinel.anomalies_total.name == sentinel.SENTINEL_METRIC_NAMES[0]
    assert sentinel.skipped_updates_total.name == \
        sentinel.SENTINEL_METRIC_NAMES[1]
    assert sentinel.rollbacks_total.name == sentinel.SENTINEL_METRIC_NAMES[2]
    assert sentinel.quarantined_windows_total.name == \
        sentinel.SENTINEL_METRIC_NAMES[3]
    assert sentinel.sdc_probes_total.name == sentinel.SDC_METRIC_NAMES[0]
    assert sentinel.sdc_mismatches_total.name == sentinel.SDC_METRIC_NAMES[1]
    # The suspect-rank exit code is a supervisor-attribution contract
    # (clear of shell/signal codes and the watchdog's abort 86).
    assert sentinel.SDC_EXIT_CODE == 87


def test_steplog_sentinel_fields_are_schema_stable():
    """The per-step JSONL stream's sentinel triple (what an incident
    reader greps first) is part of the step-record contract."""
    from dlti_tpu.telemetry.steplog import STEP_RECORD_FIELDS

    assert {"anomaly", "skipped_update", "rollbacks_total"} <= set(
        STEP_RECORD_FIELDS)


def test_steplog_goodput_fields_are_schema_stable():
    """The goodput-ledger per-phase durations (data/prefetch stall,
    device sync, checkpoint, rollback+replay) are part of the step-record
    contract: trajectory tooling attributes slow steps by these keys."""
    from dlti_tpu.telemetry.steplog import STEP_RECORD_FIELDS

    assert {"data_wait_s", "sync_s", "ckpt_s", "rollback_s"} <= set(
        STEP_RECORD_FIELDS)


def test_ledger_metric_names_are_schema_stable():
    """Goodput-ledger + critical-path attribution names are a scrape
    contract like the watchdog/ckpt sets; the bucket and phase label
    sets are parsing contracts (postmortem, steplog, /debug/slow)."""
    from dlti_tpu.telemetry import ledger

    assert ledger.LEDGER_METRIC_NAMES == (
        "dlti_goodput_fraction",
        "dlti_goodput_seconds_total",
        "dlti_goodput_mfu_percent",
    )
    assert ledger.REQUEST_PHASE_METRIC_NAMES == (
        "dlti_request_phase_seconds_total",
        "dlti_request_phase_requests_total",
    )
    assert ledger.goodput_fraction_gauge.name == \
        ledger.LEDGER_METRIC_NAMES[0]
    assert ledger.goodput_seconds_total.name == \
        ledger.LEDGER_METRIC_NAMES[1]
    assert ledger.goodput_mfu_gauge.name == ledger.LEDGER_METRIC_NAMES[2]
    assert ledger.phase_seconds_total.name == \
        ledger.REQUEST_PHASE_METRIC_NAMES[0]
    assert ledger.phase_requests_total.name == \
        ledger.REQUEST_PHASE_METRIC_NAMES[1]
    assert ledger.GOODPUT_BUCKETS == (
        "startup", "step_compute", "device_sync", "data_wait",
        "host_to_device", "eval", "checkpoint_save", "checkpoint_restore",
        "rollback", "replay", "sdc_probe", "shutdown", "other",
    )
    assert ledger.SUPERVISOR_BUCKETS == ("restart_downtime",)
    assert ledger.PRODUCTIVE_BUCKETS == ("step_compute", "device_sync")
    assert ledger.REQUEST_PHASES == (
        "gateway_queue", "queue", "tier_restore", "prefill",
        "failover", "preempt", "kv_handoff", "decode",
        "decode_prefill_stall", "other",
    )


def test_memledger_metric_names_are_schema_stable():
    """HBM memory-ledger names are a scrape contract like the
    watchdog/ckpt sets: the per-owner bytes gauge (label owner=...) plus
    the peak / headroom / untracked gauges, all registered by the server
    registry; the owner set is the attribution-label contract
    (dashboards and scripts/memory_plan.py key on it)."""
    from dlti_tpu.telemetry import memledger

    assert memledger.MEMLEDGER_METRIC_NAMES == (
        "dlti_hbm_bytes",
        "dlti_hbm_peak_bytes",
        "dlti_hbm_headroom_bytes",
        "dlti_hbm_untracked_bytes",
    )
    assert memledger.hbm_bytes_gauge.name == \
        memledger.MEMLEDGER_METRIC_NAMES[0]
    assert memledger.hbm_peak_gauge.name == \
        memledger.MEMLEDGER_METRIC_NAMES[1]
    assert memledger.hbm_headroom_gauge.name == \
        memledger.MEMLEDGER_METRIC_NAMES[2]
    assert memledger.hbm_untracked_gauge.name == \
        memledger.MEMLEDGER_METRIC_NAMES[3]
    assert memledger.MEMORY_OWNERS == (
        "params", "optimizer_state", "grad_buffers", "kv_block_pool",
        "prefix_cache_hbm", "prefetch_buffers",
        "kv_handoff_staging", "lora_adapters", "chaos_balloon",
    )


def test_adapter_metric_names_are_schema_stable():
    """Multi-LoRA serving telemetry names are a scrape contract like the
    prefix-cache set: adapter load/evict counters, pool hit/miss
    counters, and the pool slot/byte gauges, all registered by the
    server registry."""
    from dlti_tpu.serving import adapters

    assert adapters.ADAPTER_METRIC_NAMES == (
        "dlti_adapter_loads_total",
        "dlti_adapter_evictions_total",
        "dlti_adapter_pool_hits_total",
        "dlti_adapter_pool_misses_total",
        "dlti_adapter_pool_slots",
        "dlti_adapter_pool_bytes",
    )
    assert adapters.loads_total.name == adapters.ADAPTER_METRIC_NAMES[0]
    assert adapters.evictions_total.name == adapters.ADAPTER_METRIC_NAMES[1]
    assert adapters.pool_hits_total.name == adapters.ADAPTER_METRIC_NAMES[2]
    assert adapters.pool_misses_total.name == \
        adapters.ADAPTER_METRIC_NAMES[3]
    assert adapters.pool_slots_gauge.name == adapters.ADAPTER_METRIC_NAMES[4]
    assert adapters.pool_bytes_gauge.name == adapters.ADAPTER_METRIC_NAMES[5]


def test_disagg_metric_names_are_schema_stable():
    """Disaggregated-serving names are a scrape contract like the gateway
    set: per-pool liveness/queue/active gauges plus the KV-handoff
    counters and latency histogram (registered by the server registry
    when the engine is a DisaggController)."""
    from dlti_tpu.serving import disagg

    assert disagg.POOL_METRIC_NAMES == (
        "dlti_pool_prefill_replicas_alive",
        "dlti_pool_decode_replicas_alive",
        "dlti_pool_prefill_waiting",
        "dlti_pool_decode_waiting",
        "dlti_pool_prefill_active",
        "dlti_pool_decode_active",
    )
    assert disagg.KV_HANDOFF_METRIC_NAMES == (
        "dlti_kv_handoff_total",
        "dlti_kv_handoff_bytes_total",
        "dlti_kv_handoff_staged",
        "dlti_kv_handoff_fallbacks_total",
        "dlti_kv_handoff_sheds_total",
        "dlti_kv_handoff_seconds",
    )
    assert disagg.handoff_seconds.name == disagg.KV_HANDOFF_METRIC_NAMES[5]
    # Every pool_scalars key must expose as one of the pinned names.
    exposed = {f"dlti_{k}" for k in disagg.POOL_GAUGE_KEYS} | {
        "dlti_kv_handoff_total", "dlti_kv_handoff_bytes_total",
        "dlti_kv_handoff_fallbacks_total", "dlti_kv_handoff_sheds_total"}
    assert exposed == set(disagg.POOL_METRIC_NAMES
                          + disagg.KV_HANDOFF_METRIC_NAMES) - {
        "dlti_kv_handoff_seconds"}


def test_steplog_hbm_fields_are_schema_stable():
    """The per-step JSONL stream's memory pair (what an OOM incident
    reader greps first) is part of the step-record contract."""
    from dlti_tpu.telemetry.steplog import STEP_RECORD_FIELDS

    assert {"hbm_bytes_in_use", "hbm_headroom_bytes"} <= set(
        STEP_RECORD_FIELDS)


def test_heartbeat_metric_names_are_schema_stable():
    """The per-rank last-step and straggler-lag gauges are a scrape
    contract (dashboards plot which rank trails by how much)."""
    from dlti_tpu.telemetry.heartbeat import HEARTBEAT_METRIC_NAMES

    assert HEARTBEAT_METRIC_NAMES == (
        "dlti_heartbeat_last_step",
        "dlti_heartbeat_lag_steps",
    )


def test_elastic_metric_names_are_schema_stable():
    """Elastic-training telemetry names are a scrape contract like the
    watchdog/ckpt sets: the supervisor's restart counter and the
    generation / live-world gauges every generation's workers re-set."""
    from dlti_tpu.training import elastic

    assert elastic.ELASTIC_METRIC_NAMES == (
        "dlti_elastic_restarts_total",
        "dlti_elastic_generation",
        "dlti_elastic_world_size",
    )
    assert elastic.restarts_total.name == elastic.ELASTIC_METRIC_NAMES[0]
    assert elastic.generation_gauge.name == elastic.ELASTIC_METRIC_NAMES[1]
    assert elastic.world_size_gauge.name == elastic.ELASTIC_METRIC_NAMES[2]
    # The rendezvous env extension is part of the launcher contract too.
    assert elastic.ENV_GENERATION == "DLTI_GENERATION"
    assert elastic.ENV_ELASTIC_DIR == "DLTI_ELASTIC_DIR"
    assert elastic.ENV_NUM_SLOTS == "DLTI_ELASTIC_NUM_SLOTS"


def test_debug_vars_and_dump_surface_contract():
    """Keys consumers parse: the /debug/vars envelope (loadgen end-of-run
    scrape, the dashboard page) and the flight-dump file set
    (scripts/postmortem.py)."""
    from dlti_tpu.telemetry import TimeSeriesSampler
    from dlti_tpu.telemetry.flightrecorder import DUMP_FILES, MANIFEST

    snap = TimeSeriesSampler().snapshot()
    assert {"now", "interval_s", "capacity", "num_samples",
            "source_errors", "latest", "samples"} <= set(snap)
    assert DUMP_FILES == ("context.json", "spans.json", "metrics.json",
                          "timeseries.json", "config.json", "memory.json",
                          "slo.json", "deploy.json")
    assert MANIFEST == "MANIFEST.json"


def test_load_report_schema_includes_gateway_fields():
    """scripts/benchmark_serving.py consumers parse the report JSON by
    key; the multi-tenant/priority additions are part of that schema."""
    import dataclasses

    from dlti_tpu.benchmarks.loadgen import LoadReport

    fields = {f.name for f in dataclasses.fields(LoadReport)}
    required = {
        # Legacy report contract.
        "num_requests", "num_ok", "duration_s", "requests_per_s",
        "output_tokens_per_s", "latency_p50_s", "latency_p90_s",
        "latency_p99_s", "ttft_p50_s", "ttft_p90_s", "ttft_p99_s",
        "tpot_mean_ms", "errors", "server_histograms",
        # Gateway-era additions: shed accounting + per-class breakdown.
        "num_shed", "shed_rate", "per_class",
        # Watchdog-era additions: the server's own anomaly verdict from
        # the end-of-run /debug/vars scrape.
        "watchdog_alerts", "peak_queue_depth",
        # Recurring-session (prefix-tiering) additions: cold-vs-warm TTFT
        # split + the server-scraped cache hit rate.
        "num_cold", "num_warm", "cold_ttft_p50_s", "cold_ttft_p90_s",
        "warm_ttft_p50_s", "warm_ttft_p90_s", "cache_hit_rate",
        # Goodput-ledger era: server-reported critical-path phase means,
        # overall and decomposed cold-vs-warm (TTFT by phase).
        "phase_means", "cold_phases", "warm_phases",
        # Memory-ledger era: end-of-run /debug/memory scrape (owner
        # attribution + headroom).
        "memory",
        # Disaggregation era: mixed-interference mode's decode-TPOT split
        # by concurrent-long-prefill overlap.
        "interference",
        # Multi-LoRA era: per-adapter latency breakdown + the
        # server-scraped adapter-pool hit rate.
        "per_adapter", "adapter_pool_hit_rate",
        # Replica-lifecycle era: tail-of-the-tail percentiles plus the
        # per-run migration/retry disturbance totals.
        "ttft_p999_s", "tpot_p999_ms", "migrations_total", "retries_total",
        # SLO era: the /debug/slo scrape cross-checked against the
        # client's own records (server/client/agreement sections).
        "slo",
        # Adaptive-spec era: end-of-run speculative-decode economics
        # (proposed/accepted/paused totals + acceptance-rate and
        # draft-length gauges) from the /metrics scrape.
        "spec",
        # Distributed-tracing era: fraction of sampled ok requests whose
        # merged /debug/trace?request_id= timeline carries the
        # gateway + prefill + decode legs.
        "trace_coverage",
    }
    missing = required - fields
    assert not missing, f"LoadReport lost contract fields: {missing}"


def test_percentile_linear_interpolation():
    """_percentile interpolates between closest ranks (numpy's default
    method) — nearest-rank rounding snapped p99 and p99.9 to the same
    max sample at bench-sized n, hiding tail regressions."""
    from dlti_tpu.benchmarks.loadgen import _percentile

    xs = [1.0, 2.0, 3.0, 4.0]
    assert _percentile(xs, 0) == 1.0
    assert _percentile(xs, 100) == 4.0
    assert _percentile(xs, 50) == 2.5
    assert _percentile(xs, 25) == 1.75
    hundred = [float(i) for i in range(1, 101)]
    assert abs(_percentile(hundred, 99) - 99.01) < 1e-9
    assert abs(_percentile(hundred, 99.9) - 99.901) < 1e-9
    # p99 and p99.9 must now be distinguishable at n=100.
    assert _percentile(hundred, 99.9) > _percentile(hundred, 99)
    # Degenerate cases: single sample (any p) and empty.
    assert _percentile([0.25], 50) == 0.25
    assert _percentile([], 99) == 0.0


def test_per_class_summary_keys():
    """Per-priority-class breakdown keys (consumed by report tooling)."""
    from dlti_tpu.benchmarks.loadgen import RequestRecord, _class_summary

    rec = RequestRecord(start=0.0, end=1.0, first_token=0.25,
                        output_tokens=8, ok=True, status=200,
                        priority="interactive")
    shed = RequestRecord(start=0.0, end=0.1, ok=False, status=429,
                         priority="interactive", error="HTTP 429")
    summary = _class_summary([rec, shed])
    assert set(summary) == {
        "count", "ok", "shed", "latency_p50_s", "latency_p99_s",
        "ttft_p50_s", "ttft_p90_s", "ttft_p99_s", "tpot_mean_ms",
        "tpot_p99_ms",
    }
    assert summary["count"] == 2 and summary["ok"] == 1
    assert summary["shed"] == 1
    assert summary["ttft_p50_s"] == 0.25
