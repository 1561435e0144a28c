"""Serving fleet self-healing (dlti_tpu.serving.lifecycle + replicas).

Layers, mirroring the subsystem's own structure:

* **State-machine units** (fake clock, no engines): quarantine → probe
  pass/fail → reinstate, exponential probation backoff, the flap
  breaker's permanent eviction, window pruning, and the legacy
  healing-off death that must NOT book a flap.
* **Watchdog rule**: ``replica_flap`` fires on growth of the flaps
  counter in the ring, once per eviction episode, and stays silent with
  ``replica_flap_limit=0``.
* **Gateway**: drain-window-derived Retry-After on 503 refusals.
* **End-to-end heal drill**: a chaos-killed replica is quarantined,
  rebuilt, canaried against the pinned digest, reinstated, and serves
  round-2 traffic — zero client errors throughout.
* **Byte-identity**: a request live-migrated off a preempted replica
  mid-decode finishes with EXACTLY the tokens of an unmigrated run —
  greedy and seeded-sampled, bf16 and int8 KV — because the paged-KV
  handoff carries generated-so-far tokens and the slot's rng stream.
* **Rolling reload**: a multi-replica fleet hot-swaps weights one
  replica at a time under in-flight load with zero errors; same-weight
  reloads are additionally byte-identical end to end.
* **Attribution pin**: migrated/failed-over requests book the stall in
  ``stall_s``/``request_breakdown()`` as ``preempt``/``failover``, not
  as inflated decode.

The slow drill (3-replica fleet under loadgen + rolling reload + chaos
preemption) lives at the bottom under ``@pytest.mark.slow``.
"""

import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlti_tpu.config import (
    Config, GatewayConfig, MODEL_PRESETS, ReplicaLifecycleConfig,
    WatchdogConfig,
)
from dlti_tpu.models import LlamaForCausalLM
from dlti_tpu.serving import (
    AdmissionError, EngineConfig, InferenceEngine, ReplicatedEngine,
    SamplingParams,
)
from dlti_tpu.serving.gateway import AdmissionGateway
from dlti_tpu.serving.lifecycle import (
    ReplicaLifecycle, STATES, canary_digest,
)
from dlti_tpu.telemetry import (
    AnomalyWatchdog, RequestTelemetry, SpanTracer, TimeSeriesSampler,
)
from dlti_tpu.telemetry.ledger import request_breakdown

CFG = MODEL_PRESETS["llama_tiny"]

PROMPTS = [[1, 2, 3, 4, 5], [6, 7, 8], [9, 10, 11, 12], [13, 14]]


@pytest.fixture(scope="module")
def tiny_params():
    model = LlamaForCausalLM(CFG, None)
    return model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]


def _ec(**over):
    base = dict(max_seqs=4, block_size=8, num_blocks=64, max_model_len=128,
                cache_dtype="float32", eos_token_id=-1)
    base.update(over)
    return EngineConfig(**base)


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ----------------------------------------------------------------------
# State-machine units (fake clock, no engines)
# ----------------------------------------------------------------------

def test_quarantine_probe_reinstate_cycle():
    clock = _Clock()
    lc = ReplicaLifecycle(
        ReplicaLifecycleConfig(enabled=True, probation_initial_s=2.0),
        2, clock=clock)
    assert lc.state(0) == "live" and lc.state(1) == "live"
    assert lc.on_fault(1) == "quarantined"
    assert lc.due_probes() == []  # probation not yet elapsed
    clock.advance(2.0)
    assert lc.due_probes() == [1]
    lc.begin_probe(1)
    assert lc.state(1) == "probing"
    assert lc.due_probes() == []  # probing replicas are not re-offered
    assert lc.on_probe_result(1, True) == "live"
    assert lc.counters["quarantines"] == 1
    assert lc.counters["reinstates"] == 1
    assert lc.counts()["live"] == 2


def test_probation_backs_off_exponentially_and_resets_on_pass():
    clock = _Clock()
    lc = ReplicaLifecycle(
        ReplicaLifecycleConfig(probation_initial_s=1.0,
                               probation_backoff=2.0, probation_max_s=5.0,
                               flap_window_s=1e9, flap_max_cycles=100),
        1, clock=clock)
    lc.on_fault(0)
    clock.advance(1.0)
    assert lc.due_probes() == [0]
    for expect_wait in (2.0, 4.0, 5.0):  # 1 * 2**n, capped at max_s
        lc.begin_probe(0)
        lc.on_probe_result(0, False)
        clock.advance(expect_wait - 0.1)
        assert lc.due_probes() == [], expect_wait
        clock.advance(0.1)
        assert lc.due_probes() == [0], expect_wait
    lc.begin_probe(0)
    assert lc.on_probe_result(0, True) == "live"
    # A pass resets the backoff: next fault waits only the initial again.
    lc.on_fault(0)
    clock.advance(1.0)
    assert lc.due_probes() == [0]


def test_flap_breaker_evicts_after_repeated_cycles():
    clock = _Clock()
    lc = ReplicaLifecycle(
        ReplicaLifecycleConfig(probation_initial_s=0.0,
                               flap_window_s=100.0, flap_max_cycles=2),
        2, clock=clock)
    for _ in range(2):
        assert lc.on_fault(0) == "quarantined"
        lc.begin_probe(0)
        lc.on_probe_result(0, True)
        clock.advance(1.0)
    assert lc.on_fault(0) == "evicted"  # 3rd cycle inside the window
    assert lc.counters["flaps"] == 1
    assert lc.on_fault(0) == "evicted"  # terminal: no double accounting
    assert lc.counters["flaps"] == 1
    assert lc.counts()["evicted"] == 1
    assert lc.state(1) == "live"  # neighbor untouched


def test_flap_window_prunes_old_cycles():
    clock = _Clock()
    lc = ReplicaLifecycle(
        ReplicaLifecycleConfig(probation_initial_s=0.0,
                               flap_window_s=10.0, flap_max_cycles=2),
        1, clock=clock)
    for _ in range(5):  # each fault leaves the window before the next
        assert lc.on_fault(0) == "quarantined"
        lc.begin_probe(0)
        lc.on_probe_result(0, True)
        clock.advance(11.0)
    assert lc.counters["flaps"] == 0


def test_mark_dead_books_no_flap_but_evict_does():
    lc = ReplicaLifecycle(ReplicaLifecycleConfig(), 2, clock=_Clock())
    lc.mark_dead(0)  # legacy healing-off death
    assert lc.state(0) == "evicted"
    assert lc.counters["flaps"] == 0
    lc.evict(1)  # deliberate permanent removal
    assert lc.counters["flaps"] == 1


def test_canary_digest_is_stable_and_order_length_sign_sensitive():
    d = canary_digest([1, 2, 3])
    assert d == canary_digest([1, 2, 3])
    assert d != canary_digest([1, 2, 4])
    assert d != canary_digest([3, 2, 1])
    assert d != canary_digest([1, 2])
    assert canary_digest([-1]) != canary_digest([1])


def test_scalars_snapshot_keys():
    lc = ReplicaLifecycle(ReplicaLifecycleConfig(enabled=True), 3,
                          clock=_Clock())
    lc.on_fault(1)
    lc.mark_dead(2)
    s = lc.scalars()
    assert s["replica_lifecycle_quarantines_total"] == 1
    assert s["replica_lifecycle_live"] == 1
    assert s["replica_lifecycle_quarantined"] == 1
    assert s["replica_lifecycle_evicted"] == 1
    for state in STATES:
        assert f"replica_lifecycle_{state}" in s


def test_lifecycle_config_roundtrips_through_json():
    cfg = Config.from_dict({"serving": {"lifecycle": {
        "enabled": True, "flap_max_cycles": 5, "probation_initial_s": 7.5}}})
    assert cfg.serving.lifecycle.enabled
    assert cfg.serving.lifecycle.flap_max_cycles == 5
    assert cfg.serving.lifecycle.probation_initial_s == 7.5
    again = Config.from_dict(cfg.to_dict())
    assert again.serving.lifecycle == cfg.serving.lifecycle


# ----------------------------------------------------------------------
# Watchdog replica_flap rule
# ----------------------------------------------------------------------

def _watchdog(sampler, **over):
    kw = dict(enabled=True, interval_s=0.05, hung_step_min_s=30.0)
    kw.update(over)
    return AnomalyWatchdog(WatchdogConfig(**kw), sampler,
                           tracer=SpanTracer(enabled=False),
                           clock=time.monotonic)


def test_replica_flap_rule_fires_on_eviction_growth():
    s = TimeSeriesSampler(capacity=16)
    state = {"flaps": 0.0}
    s.add_source(lambda: {"dlti_replica_lifecycle_flaps_total":
                          state["flaps"]})
    wd = _watchdog(s, replica_flap_limit=1)
    s.sample_now()
    assert wd.check_now() == []  # watermark established, no alert
    state["flaps"] = 1.0
    s.sample_now()
    fired = wd.check_now()
    assert [a["rule"] for a in fired] == ["replica_flap"]
    assert "evicted" in fired[0]["message"]
    s.sample_now()
    assert wd.check_now() == []  # flat since last check: re-armed quietly
    state["flaps"] = 2.0
    s.sample_now()
    assert [a["rule"] for a in wd.check_now()] == ["replica_flap"]


def test_replica_flap_rule_disabled_by_zero_limit():
    s = TimeSeriesSampler(capacity=16)
    state = {"flaps": 0.0}
    s.add_source(lambda: {"dlti_replica_lifecycle_flaps_total":
                          state["flaps"]})
    wd = _watchdog(s, replica_flap_limit=0)
    s.sample_now()
    wd.check_now()
    state["flaps"] = 5.0
    s.sample_now()
    assert wd.check_now() == []


# ----------------------------------------------------------------------
# Gateway: drain 503 carries a drain-window-derived Retry-After
# ----------------------------------------------------------------------

class _FakeAsyncEngine:
    def __init__(self, room: int = 0):
        self.engine = types.SimpleNamespace(
            cfg=types.SimpleNamespace(max_seqs=room),
            num_active=0, waiting=[], has_work=False,
            telemetry=RequestTelemetry(), stats={}, num_free_blocks=0)
        self.submitted = []


def test_drain_503_retry_after_derived_from_drain_window():
    gw = AdmissionGateway(_FakeAsyncEngine(),
                          GatewayConfig(enabled=True, drain_grace_s=30.0,
                                        retry_after_s=1.0), None)
    try:
        gw.drain()
        with pytest.raises(AdmissionError) as ei:
            gw.submit([1], SamplingParams(), "r0")
        assert ei.value.status == 503
        # Remaining grace window, not the static 1 s backoff: a client
        # that honors it lands on the replacement process.
        assert 25.0 < ei.value.retry_after <= 30.0
    finally:
        gw.shutdown()


# ----------------------------------------------------------------------
# End-to-end: chaos-killed replica heals and serves again
# ----------------------------------------------------------------------

def _run_fleet(rep, reqs, max_steps=600):
    for _ in range(max_steps):
        if not rep.has_work:
            break
        rep.step()
    assert not rep.has_work, "fleet failed to drain its work"
    return reqs


def test_chaos_killed_replica_is_reinstated_and_serves_again(tiny_params):
    rep = ReplicatedEngine(
        CFG, tiny_params, _ec(), replicas=2, tensor=1,
        devices=jax.devices()[:2], fault_inject_step="1:3",
        lifecycle_cfg=ReplicaLifecycleConfig(enabled=True,
                                             probation_initial_s=0.0))
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    reqs = _run_fleet(rep, [rep.submit(p, sp) for p in PROMPTS])
    # Zero client errors: every round-1 request finished normally even
    # though replica 1 died mid-run (failover resubmit covered it).
    assert all(r.finish_reason == "length" for r in reqs), \
        [(r.request_id, r.finish_reason) for r in reqs]
    # Failed-over requests book the wait as "failover", not decode.
    failed_over = [r for r in reqs if r.num_retries > 0]
    assert failed_over
    for r in failed_over:
        assert r.stall_s.get("failover", 0.0) > 0.0
        assert request_breakdown(r)["phases"].get("failover", 0.0) > 0.0
    # Heal: probation 0 → the probe runs on subsequent ticks; the rebuilt
    # replica must match the pinned canary digest and come back live.
    for _ in range(10):
        if rep.lifecycle.state(1) == "live":
            break
        rep.step()
    assert rep.lifecycle.state(1) == "live"
    assert rep.lifecycle.counters["quarantines"] == 1
    assert rep.lifecycle.counters["reinstates"] == 1
    assert not rep._dead
    # Round 2: the healed replica takes traffic again.
    before = rep.engines[1].stats["requests"]
    reqs2 = _run_fleet(rep, [rep.submit(p, sp) for p in PROMPTS])
    assert all(r.finish_reason == "length" for r in reqs2)
    assert rep.engines[1].stats["requests"] > before
    assert rep.lifecycle_counts() == {
        "live": 2, "quarantined": 0, "draining": 0, "dead": 0}


# ----------------------------------------------------------------------
# Byte-identity: live migration on preemption drain
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("sp", [
    SamplingParams(max_tokens=8, temperature=0.0),           # greedy
    SamplingParams(max_tokens=8, temperature=0.9, seed=7),   # sampled
], ids=["greedy", "seeded-sampled"])
def test_migrated_outputs_byte_identical(tiny_params, kv_dtype, sp):
    """A decode live-migrated off a preempted replica mid-flight must
    finish with exactly the unmigrated run's tokens: the KV handoff
    carries generated-so-far tokens and the slot's rng stream, so not
    even a seeded sampling draw diverges."""
    ec = _ec(cache_dtype=kv_dtype)
    base = ReplicatedEngine(CFG, tiny_params, ec, replicas=2, tensor=1,
                            devices=jax.devices()[:2])
    expect = [r.output_token_ids for r in base.generate(PROMPTS, sp)]

    rep = ReplicatedEngine(CFG, tiny_params, ec, replicas=2, tensor=1,
                           devices=jax.devices()[:2],
                           fault_inject_step="1:4:preempt")
    reqs = _run_fleet(rep, [rep.submit(p, sp) for p in PROMPTS])
    assert [r.output_token_ids for r in reqs] == expect
    assert all(r.finish_reason == "length" for r in reqs)
    # The preemption actually migrated mid-decode work (not a vacuous
    # pass where the replica was idle at the chaos step).
    migrated = [r for r in reqs if r.num_migrations > 0]
    assert migrated
    assert rep.lifecycle.counters["migrations"] >= len(migrated)
    # Attribution pin: the handoff window books as "preempt" stall.
    for r in migrated:
        assert r.stall_s.get("preempt", 0.0) > 0.0
        assert request_breakdown(r)["phases"].get("preempt", 0.0) > 0.0


# ----------------------------------------------------------------------
# Rolling weight reload under live load
# ----------------------------------------------------------------------

def _drain_and_roll(rep, max_steps=2000):
    for _ in range(max_steps):
        if not rep.has_work and rep._reload is None:
            break
        rep.step()
    assert rep._reload is None, "rolling reload never completed"


def test_rolling_reload_same_weights_is_byte_identical(tiny_params):
    """Reloading the SAME weights mid-flight (re-verify + hot-swap) is a
    pure migration exercise: zero errors AND byte-identical outputs for
    every request, migrated or not."""
    sp = SamplingParams(temperature=0.0, max_tokens=16)
    prompts = [[i + 1, i + 2, i + 3] for i in range(6)]
    base = ReplicatedEngine(CFG, tiny_params, _ec(), replicas=3, tensor=1,
                            devices=jax.devices()[:3])
    expect = [r.output_token_ids for r in base.generate(prompts, sp)]

    rep = ReplicatedEngine(
        CFG, tiny_params, _ec(), replicas=3, tensor=1,
        devices=jax.devices()[:3],
        lifecycle_cfg=ReplicaLifecycleConfig(enabled=True,
                                             probation_initial_s=0.0))
    reqs = [rep.submit(p, sp) for p in prompts]
    for _ in range(3):  # get decodes in flight before the roll starts
        rep.step()
    host = jax.device_get(tiny_params)
    assert rep.request_reload(lambda: host)
    assert not rep.request_reload(lambda: host)  # roll already in progress
    _drain_and_roll(rep)
    assert all(r.finish_reason == "length" for r in reqs)
    assert [r.output_token_ids for r in reqs] == expect
    assert rep.lifecycle.counters["reinstates"] == 3
    assert rep.lifecycle.counts()["live"] == 3
    assert not rep._dead


def test_rolling_reload_swaps_new_weights_with_zero_errors(tiny_params):
    rep = ReplicatedEngine(
        CFG, tiny_params, _ec(), replicas=3, tensor=1,
        devices=jax.devices()[:3],
        lifecycle_cfg=ReplicaLifecycleConfig(enabled=True,
                                             probation_initial_s=0.0))
    sp = SamplingParams(temperature=0.0, max_tokens=12)
    reqs = [rep.submit([i + 1, i + 2, i + 3], sp) for i in range(6)]
    for _ in range(3):
        rep.step()
    new_host = jax.tree_util.tree_map(
        lambda x: np.asarray(x) * np.float32(1.01),
        jax.device_get(tiny_params))
    old_digest = rep._canary_digest
    assert rep.request_reload(lambda: new_host)
    _drain_and_roll(rep)
    # Zero client errors across the whole roll.
    assert all(r.finish_reason == "length" for r in reqs), \
        [(r.request_id, r.finish_reason) for r in reqs]
    # Every replica actually holds the new weights now.
    want = jax.tree_util.tree_leaves(new_host)[0]
    for e in rep.engines:
        got = np.asarray(jax.tree_util.tree_leaves(e.executor.params)[0])
        np.testing.assert_allclose(got, want, rtol=1e-6)
    # The canary digest was re-pinned against the new weights.
    assert rep._canary_digest is not None
    assert rep._canary_digest != old_digest
    # Fleet fully live; post-reload traffic serves normally.
    assert rep.lifecycle.counts()["live"] == 3
    out = rep.generate([[1, 2, 3]], sp)
    assert len(out[0].output_token_ids) == 12


# ----------------------------------------------------------------------
# Slow drill: 3-replica fleet under loadgen, rolling reload + chaos
# preemption, zero client errors, warm sessions stay warm
# ----------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_drill_loadgen_reload_and_preempt(tiny_params, tmp_path):
    from dlti_tpu.benchmarks import LoadGenConfig, run_load_test
    from dlti_tpu.checkpoint.store import save_pytree
    from dlti_tpu.data.tokenizer import IdTokenizer
    from dlti_tpu.serving.server import ServerConfig, make_server

    rep = ReplicatedEngine(
        CFG, tiny_params, _ec(enable_prefix_caching=True, num_blocks=128),
        replicas=3, tensor=1, devices=jax.devices()[:3],
        fault_inject_step="2:30:preempt",
        lifecycle_cfg=ReplicaLifecycleConfig(enabled=True,
                                             probation_initial_s=0.0))
    export_dir = str(tmp_path / "weights")
    save_pytree(export_dir, jax.device_get(tiny_params))
    httpd, async_engine = make_server(
        rep, IdTokenizer(vocab_size=CFG.vocab_size),
        ServerConfig(host="127.0.0.1", port=0,
                     default_params=SamplingParams(max_tokens=8),
                     gateway=GatewayConfig(enabled=True)))
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        def _kick_reload():
            import http.client
            import json as _json

            time.sleep(1.0)  # let the load build up first
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            conn.request("POST", "/v1/reload",
                         _json.dumps({"directory": export_dir}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = resp.read()
            conn.close()
            assert resp.status == 200, body

        kicker = threading.Thread(target=_kick_reload, daemon=True)
        kicker.start()
        report = run_load_test(LoadGenConfig(
            host="127.0.0.1", port=port, sessions=4, turns=4,
            max_tokens=8, stream=True, timeout_s=300,
            concurrency=4, num_requests=16))
        kicker.join(timeout=60)
        # Zero client errors: sheds (backpressure) would be tolerable,
        # hard errors are not — and there should be none of either here.
        assert not report.errors, report.errors
        assert report.num_ok == report.num_requests, \
            (report.num_ok, report.num_requests, report.errors)
        # Warm sessions stayed warm: repeat turns kept completing.
        assert report.num_warm > 0
        # Let the roll (and any preempt heal) finish, then check the
        # fleet recovered fully: all three replicas live.
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if rep._reload is None and not rep.lifecycle_pending:
                break
            time.sleep(0.2)
        assert rep._reload is None
        assert rep.lifecycle.counters["reinstates"] >= 3
        assert rep.lifecycle_counts()["live"] == 3
        assert rep.lifecycle_counts()["dead"] == 0
        # The new-fields contract rode through loadgen end to end.
        assert report.migrations_total >= 0
        assert report.ttft_p999_s >= report.ttft_p99_s
    finally:
        httpd.shutdown()
        async_engine.shutdown()
        httpd.server_close()
