"""Admission gateway tests (tiny model, CPU, ephemeral ports).

Two layers, mirroring the subsystem's own split:

* **Scheduling-policy units** against a fake engine with controllable slot
  headroom — queue bounds, per-tenant token buckets, weighted fair
  dequeue, strict priority classes, queued-deadline shed. Deterministic:
  no live decode races the assertions.
* **Full-stack integration** over real sockets — a loadgen burst past the
  queue bound sheds 429 + Retry-After while admitted requests finish;
  SIGTERM-style drain flips /health and refuses new work while in-flight
  completes; a fault-injected replica kill fails its requests over to the
  survivor with zero client-visible errors and the retries visible in
  ``dlti_gateway_retries_total``.
"""

import http.client
import json
import threading
import time
import types

import jax
import jax.numpy as jnp
import pytest

from dlti_tpu.config import GatewayConfig, MODEL_PRESETS
from dlti_tpu.data.tokenizer import IdTokenizer
from dlti_tpu.models import LlamaForCausalLM
from dlti_tpu.serving import (
    AdmissionError, EngineConfig, InferenceEngine, ReplicatedEngine,
    SamplingParams,
)
from dlti_tpu.serving.engine import Request
from dlti_tpu.serving.gateway import AdmissionGateway
from dlti_tpu.serving.server import ServerConfig, make_server
from dlti_tpu.telemetry import MetricsRegistry, RequestTelemetry

CFG = MODEL_PRESETS["llama_tiny"]


# ----------------------------------------------------------------------
# Scheduling-policy units (fake engine: no decode, controllable headroom)
# ----------------------------------------------------------------------

class _FakeAsyncEngine:
    """AsyncEngine stand-in: records dispatch order; `room` gates it."""

    def __init__(self, room: int = 0):
        self.engine = types.SimpleNamespace(
            cfg=types.SimpleNamespace(max_seqs=room),
            num_active=0, waiting=[], has_work=False,
            telemetry=RequestTelemetry(), stats={}, num_free_blocks=0)
        self.submitted = []

    def set_room(self, n: int) -> None:
        self.engine.cfg.max_seqs = n

    def submit(self, prompt_ids, params, request_id=None, q=None,
               trace_id=""):
        req = Request(request_id=request_id,
                      prompt_token_ids=list(prompt_ids),
                      params=params or SamplingParams(),
                      trace_id=trace_id)
        self.submitted.append(req)
        return req, q


def _gateway(room=0, registry=None, **overrides):
    fake = _FakeAsyncEngine(room=room)
    cfg = GatewayConfig(enabled=True, **overrides)
    gw = AdmissionGateway(fake, cfg, registry)
    return gw, fake


def _wait_for(cond, timeout=5.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting for {msg}")


def test_queue_bound_rejects_429_with_retry_after():
    gw, fake = _gateway(room=0, max_queued_requests=2, retry_after_s=3.0)
    try:
        gw.submit([1, 2], SamplingParams(), "r0")
        gw.submit([1, 2], SamplingParams(), "r1")
        with pytest.raises(AdmissionError) as ei:
            gw.submit([1, 2], SamplingParams(), "r2")
        assert ei.value.status == 429
        assert ei.value.retry_after == 3.0
        # Nothing reached the engine: the bound held the line pre-prefill.
        assert fake.submitted == []
    finally:
        gw.shutdown()


def test_queue_token_bound_rejects_429():
    gw, _ = _gateway(room=0, max_queued_requests=100, max_queued_tokens=10)
    try:
        gw.submit([0] * 6, SamplingParams(), "r0")
        with pytest.raises(AdmissionError) as ei:
            gw.submit([0] * 6, SamplingParams(), "r1")
        assert ei.value.status == 429
        assert "tokens" in ei.value.message
    finally:
        gw.shutdown()


def test_per_tenant_rate_limit_independent_buckets():
    gw, _ = _gateway(room=0, max_queued_requests=100,
                     rate_limit_rps=1.0, rate_limit_burst=2.0)
    try:
        gw.submit([1], SamplingParams(), "a0", tenant="A")
        gw.submit([1], SamplingParams(), "a1", tenant="A")
        with pytest.raises(AdmissionError) as ei:
            gw.submit([1], SamplingParams(), "a2", tenant="A")
        assert ei.value.status == 429
        # Deficit-derived Retry-After: ~1 token at 1 rps.
        assert 0 < ei.value.retry_after <= 1.1
        # Tenant B's bucket is untouched by A's burst.
        gw.submit([1], SamplingParams(), "b0", tenant="B")
        gw.submit([1], SamplingParams(), "b1", tenant="B")
    finally:
        gw.shutdown()


def test_weighted_fair_dequeue_across_tenants():
    gw, fake = _gateway(room=0, max_queued_requests=100,
                        tenant_weights="A:3,B:1")
    try:
        # A's whole burst lands first; fair dequeue must still interleave.
        for i in range(6):
            gw.submit([1], SamplingParams(), f"a{i}", tenant="A")
        for i in range(2):
            gw.submit([1], SamplingParams(), f"b{i}", tenant="B")
        fake.set_room(100)
        _wait_for(lambda: len(fake.submitted) == 8, msg="dispatch of 8")
        order = [r.request_id for r in fake.submitted]
        # Weight 3:1 -> among the first 4 dispatches, 3 of A to 1 of B
        # (stride scheduling), not A's entire FIFO burst.
        first4 = order[:4]
        assert sum(1 for rid in first4 if rid.startswith("a")) == 3, order
        assert sum(1 for rid in first4 if rid.startswith("b")) == 1, order
    finally:
        gw.shutdown()


def test_equal_weight_fairness_two_tenant_burst():
    gw, fake = _gateway(room=0, max_queued_requests=100)
    try:
        for i in range(4):
            gw.submit([1], SamplingParams(), f"a{i}", tenant="A")
        for i in range(4):
            gw.submit([1], SamplingParams(), f"b{i}", tenant="B")
        fake.set_room(100)
        _wait_for(lambda: len(fake.submitted) == 8, msg="dispatch of 8")
        order = ["ab"[r.request_id.startswith("b")]
                 for r in fake.submitted]
        # Unweighted tenants alternate: every prefix is within 1 of even.
        for k in range(1, 9):
            a, b = order[:k].count("a"), order[:k].count("b")
            assert abs(a - b) <= 1, order
    finally:
        gw.shutdown()


def test_priority_class_strictly_precedes_batch():
    gw, fake = _gateway(room=0, max_queued_requests=100)
    try:
        for i in range(3):
            gw.submit([1], SamplingParams(), f"batch{i}", priority="batch")
        for i in range(3):
            gw.submit([1], SamplingParams(), f"inter{i}",
                      priority="interactive")
        fake.set_room(100)
        _wait_for(lambda: len(fake.submitted) == 6, msg="dispatch of 6")
        order = [r.request_id for r in fake.submitted]
        assert order[:3] == ["inter0", "inter1", "inter2"], order
        assert all(rid.startswith("batch") for rid in order[3:]), order
    finally:
        gw.shutdown()


def test_unknown_priority_rejected():
    gw, _ = _gateway(room=0)
    try:
        with pytest.raises(AdmissionError) as ei:
            gw.submit([1], SamplingParams(), "r0", priority="urgent")
        assert ei.value.status == 400
    finally:
        gw.shutdown()


def test_queued_deadline_shed_before_prefill():
    registry = MetricsRegistry()
    gw, fake = _gateway(room=0, registry=registry, max_queued_requests=100)
    try:
        _, q = gw.submit([1, 2, 3], SamplingParams(), "r0", deadline_s=0.05)
        ev = q.get(timeout=5)
        assert ev[0] == "reject" and ev[1] == 503, ev
        assert "deadline" in ev[2]
        assert fake.submitted == []  # shed BEFORE any prefill
        shed = registry.counter("dlti_gateway_shed_total")
        # Sheds carry the priority label (per-class availability SLIs).
        assert shed.labels(priority="interactive").value >= 1
        stats = registry.stats_dict()
        assert stats["gateway_queue_depth"] == 0
        assert stats["gateway_queued_tokens"] == 0
    finally:
        gw.shutdown()


def test_deadline_mid_decode_sets_cancel_requested():
    gw, fake = _gateway(room=4, max_queued_requests=100)
    try:
        handle, _ = gw.submit([1, 2], SamplingParams(), "r0",
                              deadline_s=0.05)
        _wait_for(lambda: len(fake.submitted) == 1, msg="dispatch")
        req = fake.submitted[0]
        assert not req.cancel_requested
        _wait_for(lambda: req.cancel_requested, msg="deadline cancel")
        assert handle.cancel_requested
    finally:
        gw.shutdown()


def test_cancel_while_queued_never_reaches_engine():
    gw, fake = _gateway(room=0, max_queued_requests=100)
    try:
        handle, q = gw.submit([1, 2], SamplingParams(), "r0")
        handle.cancel_requested = True
        fake.set_room(10)
        ev = q.get(timeout=5)
        assert ev == ("done", "stop")
        assert fake.submitted == []
    finally:
        gw.shutdown()


def test_drain_refuses_new_admissions():
    gw, fake = _gateway(room=0, max_queued_requests=100)
    try:
        gw.submit([1], SamplingParams(), "r0")
        gw.drain()
        assert gw.draining
        with pytest.raises(AdmissionError) as ei:
            gw.submit([1], SamplingParams(), "r1")
        assert ei.value.status == 503
        assert "draining" in ei.value.message
        # Queued-pre-drain work still dispatches (accepted = finishes).
        fake.set_room(10)
        _wait_for(lambda: len(fake.submitted) == 1, msg="pre-drain dispatch")
    finally:
        gw.shutdown()


def test_gateway_metric_names_exposed():
    """Every contract name from GATEWAY_METRIC_NAMES appears in the
    Prometheus exposition once a labeled sample exists."""
    from dlti_tpu.serving.gateway import GATEWAY_METRIC_NAMES

    registry = MetricsRegistry()
    gw, _ = _gateway(room=0, registry=registry, max_queued_requests=1)
    try:
        gw.submit([1], SamplingParams(), "r0", tenant="T",
                  priority="interactive")
        with pytest.raises(AdmissionError):
            gw.submit([1], SamplingParams(), "r1")
        # Force the (labeled) shed series to exist without a real shed.
        gw._m_shed.labels(priority="interactive").inc(0)
        text = registry.render_prometheus()
        for name in GATEWAY_METRIC_NAMES:
            assert name in text, f"{name} missing from exposition"
        assert 'dlti_gateway_admitted_total{priority="interactive",tenant="T"} 1' in text
        assert ('dlti_gateway_rejected_total'
                '{priority="interactive",reason="queue_full"} 1') in text
    finally:
        gw.shutdown()


# ----------------------------------------------------------------------
# Cache-affinity routing units (fake replicas: no decode, no jit)
# ----------------------------------------------------------------------

def _fake_replicated(n: int, max_seqs: int = 4, spill_threshold: int = 4):
    """A ReplicatedEngine skeleton around load-controllable fakes — the
    routing logic under test is pure host code over engines' load/cfg."""

    def _mk(i):
        eng = types.SimpleNamespace(
            idx=i, waiting=[], num_active=0,
            cfg=types.SimpleNamespace(max_seqs=max_seqs))
        eng.submit = lambda ids, params, rid, trace_id="", _e=eng: (
            types.SimpleNamespace(request_id=rid, engine=_e,
                                  trace_id=trace_id))
        return eng

    import itertools

    rep = ReplicatedEngine.__new__(ReplicatedEngine)
    rep.engines = [_mk(i) for i in range(n)]
    rep._dead = set()
    rep._draining = set()
    rep._rr = 0
    rep._req_counter = itertools.count()
    rep.affinity_spill_threshold = spill_threshold
    rep.affinity = {"sticky": 0, "spill": 0}
    return rep


def test_affinity_rendezvous_is_sticky_and_spreads():
    rep = _fake_replicated(3)
    keys = [f"sess-{i}" for i in range(30)]
    owner = {k: rep._sticky_target(k, rep.live_engines()).idx for k in keys}
    # Deterministic: resubmitting a key always lands on the same replica.
    for k in keys:
        req = rep.submit([1, 2, 3], SamplingParams(), f"r-{k}",
                         affinity_key=k)
        assert req.engine.idx == owner[k]
    assert rep.affinity["sticky"] == 30 and rep.affinity["spill"] == 0
    # And it actually spreads sessions (not a degenerate hash).
    assert len(set(owner.values())) == 3


def test_affinity_rendezvous_stable_under_replica_death():
    """Killing one replica re-ranks ONLY the keys it owned — every other
    session keeps its (warm) target. The property that makes failover
    cheap for the fleet's caches."""
    rep = _fake_replicated(3)
    keys = [f"sess-{i}" for i in range(60)]
    before = {k: rep._sticky_target(k, rep.live_engines()).idx for k in keys}
    rep._dead.add(1)
    after = {k: rep._sticky_target(k, rep.live_engines()).idx for k in keys}
    for k in keys:
        if before[k] != 1:
            assert after[k] == before[k], f"{k} moved off a live replica"
        else:
            assert after[k] in (0, 2)  # orphans re-rank to survivors


def test_affinity_spills_least_loaded_past_backlog_threshold():
    rep = _fake_replicated(2, max_seqs=2, spill_threshold=1)
    key = "sess-hot"
    sticky = rep._sticky_target(key, rep.live_engines())
    other = next(e for e in rep.engines if e is not sticky)
    # Backlog = load - max_seqs = 4 - 2 = 2 > threshold 1: spill.
    sticky.num_active = 2
    sticky.waiting = [object(), object()]
    req = rep.submit([1], SamplingParams(), "r0", affinity_key=key)
    assert req.engine is other
    assert rep.affinity == {"sticky": 0, "spill": 1}
    # Backlog back under threshold: sticky again.
    sticky.waiting = []
    req = rep.submit([1], SamplingParams(), "r1", affinity_key=key)
    assert req.engine is sticky
    assert rep.affinity == {"sticky": 1, "spill": 1}


def test_affinity_key_from_headers_and_prefix():
    from dlti_tpu.serving.gateway import affinity_key_from

    # X-Session wins over the prompt digest.
    assert affinity_key_from({"X-Session": "abc "}, [1, 2, 3]) == "sess-abc"
    # Session-less: same prompt prefix -> same key, regardless of tail.
    k1 = affinity_key_from({}, list(range(64)), prefix_tokens=32)
    k2 = affinity_key_from({}, list(range(32)) + [99] * 32, prefix_tokens=32)
    k3 = affinity_key_from({}, [7] + list(range(63)), prefix_tokens=32)
    assert k1 == k2 and k1 != k3 and k1.startswith("pfx-")


# ----------------------------------------------------------------------
# Full-stack integration (real engine + HTTP)
# ----------------------------------------------------------------------

def _tiny_params():
    model = LlamaForCausalLM(CFG, None)
    return model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]


def _start_server(engine, gw_cfg, request_timeout_s=120.0):
    httpd, async_engine = make_server(
        engine, IdTokenizer(vocab_size=CFG.vocab_size),
        ServerConfig(host="127.0.0.1", port=0,
                     request_timeout_s=request_timeout_s,
                     default_params=SamplingParams(max_tokens=8),
                     gateway=gw_cfg))
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    return httpd, async_engine, httpd.server_address[1]


def _stop_server(httpd, async_engine):
    httpd.shutdown()
    if httpd.gateway is not None:
        httpd.gateway.shutdown()
    async_engine.shutdown()
    httpd.server_close()


def _post(port, path, body, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", path, json.dumps(body),
                 {"Content-Type": "application/json", **(headers or {})})
    resp = conn.getresponse()
    data = resp.read()
    out_headers = dict(resp.getheaders())
    conn.close()
    return resp.status, data, out_headers


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def test_loadgen_burst_sheds_429_accepted_complete():
    """Acceptance: a burst past the queue bound sheds with 429 +
    Retry-After while accepted requests complete normally."""
    from dlti_tpu.benchmarks import LoadGenConfig, run_load_test

    ec = EngineConfig(max_seqs=2, block_size=8, num_blocks=128,
                      max_model_len=128, cache_dtype="float32",
                      eos_token_id=-1)
    engine = InferenceEngine(CFG, _tiny_params(), ec)
    gw_cfg = GatewayConfig(enabled=True, max_queued_requests=3,
                           retry_after_s=2.0)
    httpd, aeng, port = _start_server(engine, gw_cfg)
    try:
        report = run_load_test(LoadGenConfig(
            host="127.0.0.1", port=port, num_requests=24, concurrency=24,
            max_tokens=16, stream=False, prompt="burst", timeout_s=120))
        # Every request either completed or was deliberately shed — the
        # burst produced no real errors.
        assert report.num_ok + report.num_shed == 24, report.errors
        assert report.num_ok >= 1
        assert report.num_shed >= 1, "burst never exceeded the queue bound"
        assert report.shed_rate == pytest.approx(report.num_shed / 24,
                                                 abs=1e-4)
        assert report.errors == [], report.errors
        # Direct probe for the Retry-After header on a shed response:
        # stall the queue (slots busy with the long default) then overfill.
        status, data, headers = _post(port, "/v1/completions", {
            "prompt": "x", "max_tokens": 1, "temperature": 0.0})
        assert status == 200, data
    finally:
        _stop_server(httpd, aeng)


def test_loadgen_multitenant_priority_mix_report():
    """Satellite: --tenants/--priority-mix drive the gateway end to end
    and the report carries per-class latency percentiles."""
    from dlti_tpu.benchmarks import LoadGenConfig, run_load_test

    ec = EngineConfig(max_seqs=4, block_size=8, num_blocks=128,
                      max_model_len=128, cache_dtype="float32",
                      eos_token_id=-1)
    engine = InferenceEngine(CFG, _tiny_params(), ec)
    gw_cfg = GatewayConfig(enabled=True, max_queued_requests=64)
    httpd, aeng, port = _start_server(engine, gw_cfg)
    try:
        report = run_load_test(LoadGenConfig(
            host="127.0.0.1", port=port, num_requests=12, concurrency=4,
            max_tokens=4, stream=True, prompt="mix", timeout_s=120,
            tenants=3, priority_mix="interactive:0.5,batch:0.5"))
        assert report.num_ok == 12, report.errors
        assert set(report.per_class) == {"interactive", "batch"}
        total = sum(c["count"] for c in report.per_class.values())
        assert total == 12
        for cls in report.per_class.values():
            if cls["ok"]:
                assert cls["ttft_p50_s"] > 0
        # Both priority classes and all three tenants hit the gateway.
        stats = json.loads(_get(port, "/stats")[1])
        keys = [k for k in stats
                if k.startswith("dlti_gateway_admitted_total")]
        assert any("tenant-0" in k for k in keys), keys
        assert any("tenant-2" in k for k in keys), keys
    finally:
        _stop_server(httpd, aeng)


def test_http_429_carries_retry_after_header():
    ec = EngineConfig(max_seqs=2, block_size=8, num_blocks=64,
                      max_model_len=64, cache_dtype="float32",
                      eos_token_id=-1)
    engine = InferenceEngine(CFG, _tiny_params(), ec)
    # Deterministic refusal: burst capacity 1 at a glacial refill.
    gw_cfg = GatewayConfig(enabled=True, rate_limit_rps=0.01,
                           rate_limit_burst=1.0)
    httpd, aeng, port = _start_server(engine, gw_cfg)
    try:
        status, data, _ = _post(port, "/v1/completions",
                                {"prompt": "a", "max_tokens": 2})
        assert status == 200, data
        status, data, headers = _post(port, "/v1/completions",
                                      {"prompt": "a", "max_tokens": 2})
        assert status == 429, data
        assert "rate limit" in json.loads(data)["error"]["message"]
        assert int(headers["Retry-After"]) >= 1
        # The unlimited default tenant is a different principal: an
        # X-Tenant'd client refusal never blocks another tenant.
        status, _, _ = _post(port, "/v1/completions",
                             {"prompt": "a", "max_tokens": 2},
                             headers={"X-Tenant": "other"})
        assert status == 200
    finally:
        _stop_server(httpd, aeng)


def test_drain_flips_health_and_finishes_inflight():
    ec = EngineConfig(max_seqs=2, block_size=8, num_blocks=64,
                      max_model_len=64, cache_dtype="float32",
                      eos_token_id=-1)
    engine = InferenceEngine(CFG, _tiny_params(), ec)
    gw_cfg = GatewayConfig(enabled=True, drain_grace_s=30.0)
    httpd, aeng, port = _start_server(engine, gw_cfg)
    try:
        assert _get(port, "/health")[0] == 200
        results = {}

        def _inflight():
            results["resp"] = _post(port, "/v1/completions", {
                "prompt": "abc", "max_tokens": 24, "temperature": 0.0})

        t = threading.Thread(target=_inflight)
        t.start()
        # Wait until the request is actually in the system, then drain —
        # the same sequence serve()'s SIGTERM handler runs.
        _wait_for(lambda: engine.has_work, msg="in-flight request")
        httpd.gateway.drain()
        status, data = _get(port, "/health")
        assert status == 503
        assert json.loads(data)["status"] == "draining"
        status, data, headers = _post(port, "/v1/completions",
                                      {"prompt": "new", "max_tokens": 2})
        assert status == 503
        assert "draining" in json.loads(data)["error"]["message"]
        assert "Retry-After" in headers
        t.join(timeout=60)
        assert results["resp"][0] == 200, "in-flight request must finish"
        assert httpd.gateway.wait_idle(30.0)
    finally:
        _stop_server(httpd, aeng)


def test_health_reports_dead_engine():
    """Satellite: /health must 503 once the stepper parks itself — a load
    balancer kept routing to a corpse on the old unconditional 200."""
    ec = EngineConfig(max_seqs=2, block_size=8, num_blocks=64,
                      max_model_len=64, cache_dtype="float32",
                      eos_token_id=-1)
    engine = InferenceEngine(CFG, _tiny_params(), ec)
    httpd, aeng, port = _start_server(engine, None)
    try:
        assert _get(port, "/health")[0] == 200
        aeng._dead = True  # the state abort-failure recovery leaves behind
        status, data = _get(port, "/health")
        assert status == 503
        assert json.loads(data)["status"] == "dead"
    finally:
        aeng._stop = True
        _stop_server(httpd, aeng)


def test_request_timeout_cancels_engine_request():
    """Satellite: request_timeout_s expiry must set cancel_requested —
    the engine releases the slot instead of decoding to max_tokens."""
    ec = EngineConfig(max_seqs=2, block_size=8, num_blocks=128,
                      max_model_len=128, cache_dtype="float32",
                      eos_token_id=-1)
    engine = InferenceEngine(CFG, _tiny_params(), ec)
    httpd, aeng, port = _start_server(engine, None, request_timeout_s=0.05)
    try:
        status, data, _ = _post(port, "/v1/completions", {
            "prompt": "abc", "max_tokens": 100, "temperature": 0.0})
        assert status == 500
        assert "timed out" in json.loads(data)["error"]["message"]
        # The cancel drains the request within one decode window: the
        # engine empties long before 100 tokens' worth of steps.
        _wait_for(lambda: not engine.has_work, timeout=30,
                  msg="engine drained after timeout cancel")
        req = next(r for r in engine.finished)
        assert len(req.output_token_ids) < 100
    finally:
        _stop_server(httpd, aeng)


# ----------------------------------------------------------------------
# Replica failover
# ----------------------------------------------------------------------

def test_replica_fault_fails_over_offline_generate(devices):
    """Satellite: one replica's step() fault must not orphan the other
    replica's requests — stranded requests finish on the survivor."""
    ec = EngineConfig(max_seqs=4, block_size=8, num_blocks=64,
                      max_model_len=64, cache_dtype="float32",
                      eos_token_id=-1)
    rep = ReplicatedEngine(CFG, _tiny_params(), ec, replicas=2, tensor=1,
                           devices=devices[:2], max_retries=2,
                           fault_inject_step="0:2")
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8], [9, 10]]
    results = rep.generate(prompts, SamplingParams(max_tokens=6,
                                                   temperature=0.0))
    assert rep.num_live == 1
    assert rep.failover["replica_faults"] == 1
    assert rep.failover["retries"] >= 1
    for r in results:
        assert r.finish_reason == "length", r
        assert len(r.output_token_ids) == 6
    # The survivor keeps serving new work.
    more = rep.generate([[2, 4, 6]], SamplingParams(max_tokens=3,
                                                    temperature=0.0))
    assert more[0].finish_reason == "length"


def test_replica_fault_exhausted_retries_error_not_hang(devices):
    """Both replicas down: requests finish as errors instead of hanging
    the drain loop or crashing the caller."""
    ec = EngineConfig(max_seqs=4, block_size=8, num_blocks=64,
                      max_model_len=64, cache_dtype="float32",
                      eos_token_id=-1)
    rep = ReplicatedEngine(CFG, _tiny_params(), ec, replicas=2, tensor=1,
                           devices=devices[:2], max_retries=2)
    for eng in rep.engines:
        eng.step = lambda: (_ for _ in ()).throw(
            RuntimeError("injected: both replicas die"))
    results = rep.generate([[1, 2, 3], [4, 5, 6]],
                           SamplingParams(max_tokens=4))
    assert rep.num_live == 0
    assert all(r.finish_reason in ("error", "abort") for r in results)
    with pytest.raises(RuntimeError):
        rep.submit([1, 2], SamplingParams())


def test_replica_warmup_aot_stays_engaged_off_default_device(devices):
    """Regression (found driving scripts/serve.py --replicas 2): warmup's
    AOT lowering must carry each replica's actual placement — lowered on
    plain avals it compiled for device 0, and replica 1's pinned params
    made its first decode step raise a sharding-mismatch ValueError that
    read as a replica fault and killed the replica at startup. Both
    replicas must warm up, keep the AOT dispatch path, and emit the same
    greedy stream."""
    ec = EngineConfig(max_seqs=4, block_size=8, num_blocks=64,
                      max_model_len=64, cache_dtype="float32",
                      eos_token_id=-1)
    params = _tiny_params()
    rep = ReplicatedEngine(CFG, params, ec, replicas=2, tensor=1,
                           devices=devices[:2])
    rep.warmup_decode_ladder()
    res = rep.generate([[1, 2, 3], [4, 5, 6]],
                       SamplingParams(max_tokens=5, temperature=0.0))
    assert rep.num_live == 2 and rep.failover["replica_faults"] == 0
    for eng in rep.engines:
        assert eng.executor._decode_fn._aot_state["aot"], \
            "replica fell off the AOT decode path"
    # Placement agrees end to end: each replica's KV pool is committed to
    # its own params' device (jit migration no longer papers over it).
    for eng in rep.engines:
        p_dev = next(iter(jax.tree_util.tree_leaves(eng.executor.params)[0].devices()))
        c_dev = next(iter(jax.tree_util.tree_leaves(eng.executor.cache)[0].devices()))
        assert p_dev == c_dev
    single = InferenceEngine(CFG, params, ec).generate(
        [[1, 2, 3]], SamplingParams(max_tokens=5, temperature=0.0))
    assert single[0].output_token_ids == res[0].output_token_ids


def test_replica_kill_failover_through_server(devices):
    """Acceptance: with affinity routing on and one replica fault-injected
    mid-run, its in-flight requests complete on the survivor — client
    error rate from the fault is 0, the retries are visible in
    dlti_gateway_retries_total, and sessions that were sticky to the dead
    replica re-route to the survivor and still complete."""
    ec = EngineConfig(max_seqs=4, block_size=8, num_blocks=128,
                      max_model_len=128, cache_dtype="float32",
                      eos_token_id=-1)
    rep = ReplicatedEngine(CFG, _tiny_params(), ec, replicas=2, tensor=1,
                           devices=devices[:2], max_retries=2,
                           fault_inject_step="0:3")
    gw_cfg = GatewayConfig(enabled=True, max_queued_requests=64,
                           affinity=True)
    # With 2 replicas, 6 sessions hash to both sides — some are sticky to
    # the replica the chaos hook is about to kill.
    sessions = [f"sess-{i}" for i in range(6)]
    doomed = [s for s in sessions
              if rep._sticky_target("sess-" + s, rep.live_engines())
              is rep.engines[0]]
    assert doomed, "rendezvous hash left replica 0 unused; test is vacuous"
    httpd, aeng, port = _start_server(rep, gw_cfg)
    try:
        results = [None] * 6

        def _one(i):
            results[i] = _post(
                port, "/v1/completions",
                {"prompt": f"req {i}", "max_tokens": 12, "temperature": 0.0},
                headers={"X-Session": sessions[i]})

        threads = [threading.Thread(target=_one, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        for i, r in enumerate(results):
            assert r is not None and r[0] == 200, (i, r)
            obj = json.loads(r[1])
            assert obj["usage"]["completion_tokens"] == 12, obj
        assert rep.num_live == 1
        assert rep.failover["retries"] >= 1
        assert rep.affinity["sticky"] >= 1

        # Sessions sticky to the DEAD replica re-route: rendezvous over
        # the survivors now owns them, and their follow-up turns complete
        # with zero client errors.
        for s in doomed:
            status, data, _ = _post(
                port, "/v1/completions",
                {"prompt": f"follow-up {s}", "max_tokens": 6,
                 "temperature": 0.0},
                headers={"X-Session": s})
            assert status == 200, (s, status, data)
            assert json.loads(data)["usage"]["completion_tokens"] == 6

        # Retries + affinity counters are on /metrics under contract names.
        status, data = _get(port, "/metrics")
        assert status == 200
        text = data.decode()
        line = next(l for l in text.splitlines()
                    if l.startswith("dlti_gateway_retries_total "))
        assert float(line.split()[1]) >= 1
        line = next(l for l in text.splitlines()
                    if l.startswith("dlti_gateway_replicas_alive "))
        assert float(line.split()[1]) == 1
        line = next(l for l in text.splitlines()
                    if l.startswith("dlti_gateway_affinity_sticky_total "))
        assert float(line.split()[1]) >= 1
    finally:
        _stop_server(httpd, aeng)
