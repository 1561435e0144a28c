"""Unified telemetry layer tests (tier-1).

Covers the four contracts the subsystem makes:

* **Exposition stability** — the registry-backed ``/metrics`` keeps every
  pre-existing ``dlti_<stat>`` name and TYPE byte-for-byte (golden test
  against the legacy inline renderer), and adds the TTFT/TPOT/queue-time
  histograms.
* **Tracer bounds + format** — the span ring buffer never exceeds its
  capacity, and exports load as valid Chrome-trace JSON (``ph``/``ts``/
  ``name`` on every event) viewable in Perfetto.
* **Engine lifecycle ordering** — a served request's spans appear in
  submitted → queued → prefill → decode order with matching histogram
  observations.
* **Disabled-path overhead** — a disabled tracer's span site costs an
  attribute read (bounded well under the noise floor of a decode step).

Plus the training-side stream: the per-step JSONL schema stays a superset
of the reference CSV columns (the parity contract in
``dlti_tpu/utils/metrics.py``), verified both statically and from a real
tiny training run that also exercises ``--trace-dir``'s per-step phase
spans.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlti_tpu.config import (
    CheckpointConfig, Config, DataConfig, LoRAConfig, MODEL_PRESETS,
    TelemetryConfig, TrainConfig,
)
from dlti_tpu.models import LlamaForCausalLM
from dlti_tpu.serving import EngineConfig, InferenceEngine, SamplingParams
from dlti_tpu.telemetry import (
    Heartbeat, MetricsRegistry, SpanTracer, configure_tracer, get_tracer,
    jsonl_stream_columns, metrics_csv_columns, schedule_lr,
)
from dlti_tpu.telemetry.registry import Histogram
from dlti_tpu.utils.metrics import REFERENCE_CSV_COLUMNS

CFG = MODEL_PRESETS["llama_tiny"]


# ----------------------------------------------------------------------
# Registry
# ----------------------------------------------------------------------

# A stats dict shaped like the engine's (every key the legacy inline
# exposition rendered), with the derived gauges the server adds.
FAKE_STATS = {
    "requests": 3, "generated_tokens": 12, "prefill_tokens": 9,
    "preemptions": 0, "decode_steps": 4, "decode_slot_steps": 7,
    "prefix_cached_tokens": 0, "spec_proposed": 0, "spec_accepted": 0,
    "spec_paused_rounds": 0,
    "active_seqs": 1, "waiting": 2, "free_blocks": 100,
}
GAUGE_KEYS = ("active_seqs", "waiting", "free_blocks")


def _legacy_exposition(stats: dict) -> str:
    """The exact renderer serving/server.py inlined before the registry."""
    lines = []
    for k, v in sorted(stats.items()):
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        name = f"dlti_{k}"
        kind = "gauge" if k in GAUGE_KEYS else "counter"
        lines += [f"# TYPE {name} {kind}", f"{name} {v}"]
    return "\n".join(lines) + "\n"


def test_registry_exposition_matches_legacy_renderer():
    """Golden: with only the scalar source registered, the registry
    reproduces the legacy /metrics output byte-for-byte."""
    reg = MetricsRegistry()
    reg.add_scalar_source(lambda: dict(FAKE_STATS), gauge_keys=GAUGE_KEYS,
                          prefix="dlti_")
    assert reg.render_prometheus() == _legacy_exposition(FAKE_STATS)


def test_registry_exposition_with_histograms_keeps_legacy_lines():
    """Adding histograms must not rename or retype any legacy series."""
    reg = MetricsRegistry()
    reg.add_scalar_source(lambda: dict(FAKE_STATS), gauge_keys=GAUGE_KEYS,
                          prefix="dlti_")
    h = Histogram("dlti_request_ttft_seconds", (0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    reg.register(h)
    text = reg.render_prometheus()
    legacy_lines = _legacy_exposition(FAKE_STATS).strip().splitlines()
    new_lines = text.strip().splitlines()
    # Every legacy line survives verbatim, in the same relative order.
    it = iter(new_lines)
    for want in legacy_lines:
        for got in it:
            if got == want:
                break
        else:
            pytest.fail(f"legacy exposition line missing/reordered: {want}")
    # Histogram series render in Prometheus histogram format, cumulative.
    assert "# TYPE dlti_request_ttft_seconds histogram" in text
    assert 'dlti_request_ttft_seconds_bucket{le="0.1"} 1' in text
    assert 'dlti_request_ttft_seconds_bucket{le="1"} 2' in text
    assert 'dlti_request_ttft_seconds_bucket{le="+Inf"} 3' in text
    assert "dlti_request_ttft_seconds_count 3" in text


def test_registry_stats_dict_merges_sources_and_summaries():
    reg = MetricsRegistry()
    reg.add_scalar_source(lambda: dict(FAKE_STATS), gauge_keys=GAUGE_KEYS,
                          prefix="dlti_")
    h = Histogram("dlti_request_ttft_seconds", (0.1, 1.0),
                  stats_key="request_ttft_seconds")
    h.observe(0.2)
    reg.register(h)
    d = reg.stats_dict()
    assert d["requests"] == 3 and d["free_blocks"] == 100
    s = d["request_ttft_seconds"]
    assert s["count"] == 1 and s["mean"] == pytest.approx(0.2)
    assert set(s) >= {"count", "sum", "mean", "p50", "p90", "p99"}


def test_histogram_percentiles_and_labels():
    h = Histogram("h", (1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0):
        h.observe(v)
    assert 0.0 < h.percentile(50) <= 2.0
    assert h.percentile(99) <= 4.0
    reg = MetricsRegistry()
    g = reg.gauge("dlti_heartbeat_last_step")
    g.labels(process="0").set(7)
    g.labels(process="1").set(5)
    text = reg.render_prometheus()
    assert 'dlti_heartbeat_last_step{process="0"} 7' in text
    assert 'dlti_heartbeat_last_step{process="1"} 5' in text


# ----------------------------------------------------------------------
# Tracer
# ----------------------------------------------------------------------

def test_tracer_ring_buffer_bounded(tmp_path):
    tr = SpanTracer(capacity=100, enabled=True)
    for i in range(250):
        tr.instant(f"e{i}")
    assert len(tr) == 100
    # Oldest dropped: the survivors are the most recent 100.
    names = [e["name"] for e in tr.events()]
    assert names[0] == "e150" and names[-1] == "e249"


def test_tracer_chrome_export_valid(tmp_path):
    tr = SpanTracer(capacity=64, enabled=True)
    with tr.span("phase_a", cat="test", step=1):
        pass
    tr.complete("phase_b", 1.0, 2.0, cat="test")
    tr.instant("mark")
    path = tr.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        d = json.load(f)  # must be valid JSON
    evs = d["traceEvents"]
    assert len(evs) == 3
    for ev in evs:
        assert {"ph", "ts", "name", "pid", "tid"} <= set(ev)
    spans = [e for e in evs if e["ph"] == "X"]
    assert all("dur" in e and e["dur"] >= 0 for e in spans)
    b = next(e for e in evs if e["name"] == "phase_b")
    assert b["ts"] == pytest.approx(1.0e6) and b["dur"] == pytest.approx(1.0e6)


def test_tracer_disabled_overhead_smoke():
    """The disabled span site must be unmeasurable against a decode step:
    bound the per-call cost at 20 µs (measured ~0.3 µs; the bound only
    exists to catch an accidental dict/lock/clock on the disabled path)."""
    tr = SpanTracer(enabled=False)
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        with tr.span("hot"):
            pass
        tr.instant("hot")
        tr.complete("hot", 0.0, 1.0)
    dt = time.perf_counter() - t0
    assert len(tr) == 0  # nothing recorded
    assert dt / n < 20e-6, f"disabled-path cost {dt / n * 1e6:.2f} us/site"


def test_configure_tracer_resizes_and_toggles():
    tr = configure_tracer(enabled=True, capacity=8)
    try:
        assert tr is get_tracer()
        for i in range(20):
            tr.instant(f"x{i}")
        assert len(tr) == 8
    finally:
        configure_tracer(enabled=False)
        tr.clear()


# ----------------------------------------------------------------------
# Engine request lifecycle
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_engine():
    """Tiny engine driven through a few requests with tracing enabled."""
    model = LlamaForCausalLM(CFG, None)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    ec = EngineConfig(max_seqs=2, block_size=8, num_blocks=64,
                      max_model_len=64, cache_dtype="float32",
                      eos_token_id=-1)
    tracer = configure_tracer(enabled=True, capacity=4096)
    tracer.clear()
    engine = InferenceEngine(CFG, params, ec)
    prompts = [[5, 6, 7], [9, 10], [11, 12, 13, 14]]
    results = engine.generate(prompts,
                              SamplingParams(max_tokens=4, temperature=0.0))
    yield engine, results, tracer.events()
    configure_tracer(enabled=False)
    tracer.clear()


def test_request_lifecycle_span_ordering(traced_engine):
    engine, results, events = traced_engine
    assert all(r.finish_reason == "length" for r in results)
    for r in results:
        mine = [e for e in events
                if e.get("args", {}).get("id") == r.request_id]
        by_name = {e["name"]: e for e in mine}
        assert {"request/submitted", "request/queued", "request/prefill",
                "request/decode"} <= set(by_name), by_name.keys()
        sub = by_name["request/submitted"]
        q, p, d = (by_name["request/queued"], by_name["request/prefill"],
                   by_name["request/decode"])
        # Phase ordering: each phase starts no earlier than the previous
        # one began, and spans chain start -> end -> next start.
        assert sub["ts"] <= q["ts"] + q["dur"]
        assert q["ts"] <= p["ts"] and p["ts"] <= d["ts"]
        assert q["ts"] + q["dur"] <= p["ts"] + p["dur"] + 1e-3
        assert d["args"]["output_tokens"] == 4
        assert d["args"]["finish_reason"] == "length"


def test_engine_step_phase_spans_present(traced_engine):
    _, _, events = traced_engine
    names = {e["name"] for e in events}
    assert "engine/decode_prep" in names
    assert "engine/admit" in names
    assert "engine/decode_wait" in names


def test_lifecycle_histograms_observed(traced_engine):
    engine, results, _ = traced_engine
    tel = engine.telemetry
    n = len(results)
    assert tel.ttft.snapshot()[2] == n
    assert tel.queue_time.snapshot()[2] == n
    assert tel.tpot.snapshot()[2] == n  # every request emitted > 1 token
    # max_tokens=4 -> 3 inter-token gaps per request, all positive.
    assert tel.tpot.summary()["mean"] > 0


def test_server_registry_backing(traced_engine):
    """build_registry over a live engine: legacy names + histograms in one
    exposition, /stats served from the same store."""
    engine, _, _ = traced_engine

    class _FakeAsync:  # build_registry only reads .engine
        pass

    fake = _FakeAsync()
    fake.engine = engine
    from dlti_tpu.serving.server import build_registry

    reg = build_registry(fake)
    text = reg.render_prometheus()
    assert "# TYPE dlti_requests counter" in text
    assert "# TYPE dlti_free_blocks gauge" in text
    assert "# TYPE dlti_request_ttft_seconds histogram" in text
    assert "# TYPE dlti_request_tpot_seconds histogram" in text
    assert "# TYPE dlti_request_queue_time_seconds histogram" in text
    d = reg.stats_dict()
    assert d["requests"] == engine.stats["requests"]
    assert d["request_ttft_seconds"]["count"] == 3


# ----------------------------------------------------------------------
# Heartbeat
# ----------------------------------------------------------------------

def test_heartbeat_single_process_and_gauges():
    reg = MetricsRegistry()
    hb = Heartbeat(registry=reg)
    hb.beat(10)
    assert hb.last_seen[0][0] == 10
    assert hb.lag() == 0 and hb.straggler_report() is None
    # Straggler arithmetic on an injected multi-process view.
    hb.last_seen[1] = (7, time.time())
    assert hb.lag() == 3
    assert "proc 1: -3" in hb.straggler_report()
    text = reg.render_prometheus()
    assert 'dlti_heartbeat_last_step{process="0"} 10' in text


def test_heartbeat_straggler_report_and_lag_gauge():
    """straggler_report() had no unit test (log-only until the lag
    gauge); pin its text + the per-rank lags()/gauge surface."""
    reg = MetricsRegistry()
    hb = Heartbeat(registry=reg)
    # Lockstep fleet: no report, zero lags.
    now = time.time()
    hb.last_seen = {0: (12, now), 1: (12, now)}
    assert hb.straggler_report() is None
    assert hb.lags() == {0: 0, 1: 0}
    # Two stragglers at different depths: the report names each with its
    # deficit, sorted by rank; lags() is the gauge form of the same view.
    hb.last_seen = {0: (12, now), 1: (9, now), 2: (5, now)}
    report = hb.straggler_report()
    assert "behind step 12" in report
    assert "proc 1: -3" in report and "proc 2: -7" in report
    assert hb.lags() == {0: 0, 1: 3, 2: 7}
    # beat() refreshes both gauges; per-rank lag is exposed for scrape.
    hb.beat(12)
    text = reg.render_prometheus()
    assert 'dlti_heartbeat_lag_steps{process="0"} 0' in text
    assert 'dlti_heartbeat_lag_steps{process="2"} 7' in text
    # Empty map degrades cleanly.
    hb.last_seen = {}
    assert hb.lags() == {} and hb.lag() == 0
    assert hb.straggler_report() is None


# ----------------------------------------------------------------------
# Per-step JSONL stream: schema superset of the reference CSV
# ----------------------------------------------------------------------

def test_jsonl_schema_superset_of_reference_csv():
    cols = jsonl_stream_columns()
    assert set(REFERENCE_CSV_COLUMNS) <= cols
    # ... and of the extended CSV (MetricsRecord) too.
    assert set(metrics_csv_columns()) <= cols


def test_schedule_lr_matches_optax():
    import dataclasses

    from dlti_tpu.config import OptimizerConfig
    from dlti_tpu.training.optimizer import build_schedule

    for kwargs in ({"schedule": "warmup_constant", "warmup_steps": 10},
                   {"schedule": "warmup_cosine", "warmup_steps": 5,
                    "total_steps": 50}):
        cfg = OptimizerConfig(learning_rate=3e-4, **kwargs)
        sched = build_schedule(cfg)
        for step in (0, 1, 5, 10, 25, 50, 80):
            assert schedule_lr(cfg, step) == pytest.approx(
                float(sched(step)), rel=1e-5), (kwargs, step)


def test_training_smoke_writes_stream_and_trace(tmp_path):
    """Tiny end-to-end train with telemetry on: the JSONL stream has
    run/step/final records (final ⊇ reference CSV columns) and the trace
    dir gets a Perfetto-loadable Chrome trace with per-step phase spans —
    the acceptance criterion for ``--trace-dir``."""
    from dlti_tpu.training import Trainer

    cfg = Config(
        model=CFG,
        lora=LoRAConfig(enabled=False),
        data=DataConfig(max_seq_len=16),
        checkpoint=CheckpointConfig(save_strategy="no"),
        train=TrainConfig(num_epochs=1, micro_batch_size=2,
                          grad_accum_steps=1, max_steps=2, logging_steps=1),
        telemetry=TelemetryConfig(
            trace_dir=str(tmp_path / "traces"),
            step_log_path=str(tmp_path / "steps.jsonl"),
            heartbeat_interval_steps=1),
    )
    rng = np.random.default_rng(0)
    ids = [rng.integers(1, 500, (1, 2, 16), dtype=np.int32)
           for _ in range(3)]
    batches = [{"input_ids": a, "labels": a} for a in ids]
    try:
        trainer = Trainer(cfg)
        _, record = trainer.train(batches_per_epoch=batches)
    finally:
        configure_tracer(enabled=False)
        get_tracer().clear()

    lines = [json.loads(l) for l in open(tmp_path / "steps.jsonl")]
    assert [l["type"] for l in lines] == ["run", "step", "step", "final"]
    from dlti_tpu.telemetry.steplog import STEP_RECORD_FIELDS

    for step_rec in lines[1:-1]:
        assert set(STEP_RECORD_FIELDS) <= set(step_rec)
        assert step_rec["loss"] > 0
    final = lines[-1]
    assert set(REFERENCE_CSV_COLUMNS) <= set(final)
    assert final["final_loss"] == pytest.approx(record.final_loss)

    traces = list((tmp_path / "traces").glob("*.json"))
    assert len(traces) == 1
    with open(traces[0]) as f:
        d = json.load(f)
    names = {e["name"] for e in d["traceEvents"]}
    assert {"train/batch_fetch", "train/step_dispatch",
            "train/device_sync"} <= names
    for ev in d["traceEvents"]:
        assert {"ph", "ts", "name"} <= set(ev)
