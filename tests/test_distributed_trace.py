"""Distributed-tracing units (telemetry.distributed_trace): fake-clock
clock-offset estimation, span federation, per-request timeline
reconstruction, trace-context wire round-trips, and flight-dump merging.

All deterministic — RPC round trips are *simulated* with explicit fake
clocks (a true offset we control), so the estimator's invariant
(|estimate − truth| ≤ uncertainty) is checked against ground truth rather
than wall time. The cross-process integration drill (real fleet, real
spans over the wire protocol) lives in tests/test_fleet.py.
"""

import math
import random

import pytest

from dlti_tpu.serving.engine import Request
from dlti_tpu.serving.sampling import SamplingParams
from dlti_tpu.serving.wire import request_from_wire, request_to_wire
from dlti_tpu.telemetry.distributed_trace import (
    SEQUENTIAL_LEGS, ClockOffsetEstimator, TraceFederator, merge_dump_tails,
    mint_trace_id, request_timeline,
)
from dlti_tpu.telemetry.tracer import SpanTracer


# ----------------------------------------------------------------------
# Fake-clock RPC simulation
# ----------------------------------------------------------------------

def _simulate_rpcs(est, true_offset, rtts, *, local_t0=100.0,
                   asymmetry=0.5, drift_per_rpc=0.0):
    """Feed simulated round trips into ``est`` against a worker whose
    clock reads ``local − true_offset`` (optionally drifting). Returns
    the final true offset (it moves when drift_per_rpc != 0)."""
    t = local_t0
    off = true_offset
    for i, rtt in enumerate(rtts):
        t0 = t
        t1 = t + rtt
        # The worker stamps its clock somewhere inside the window; the
        # asymmetry knob places it (0.5 = symmetric legs).
        remote_stamp = (t0 + asymmetry * rtt) - off
        est.sample(t0, t1, remote_stamp)
        t = t1 + 0.01
        off += drift_per_rpc
    return off


def test_estimator_converges_on_skewed_worker():
    """A worker whose clock is 3.5s behind: the estimate lands within
    half-RTT of the truth and the invariant holds after every sample."""
    est = ClockOffsetEstimator()
    true = 3.5
    rtts = [0.004, 0.002, 0.003, 0.005, 0.002, 0.004, 0.003, 0.002]
    _simulate_rpcs(est, true, rtts)
    assert est.samples == len(rtts)
    assert abs(est.offset - true) <= est.uncertainty
    assert abs(est.offset - true) < 0.01
    assert est.to_dict()["uncertainty_s"] == pytest.approx(est.uncertainty)


def test_estimator_invariant_under_asymmetric_legs():
    """However asymmetric the two legs of each RPC are, the remote stamp
    was taken inside the [t0, t1] window — so |estimate − truth| stays
    within the (smoothed half-RTT) uncertainty, sample by sample."""
    rng = random.Random(7)
    est = ClockOffsetEstimator()
    true = -1.25                     # worker clock AHEAD of supervisor
    t = 50.0
    for _ in range(64):
        rtt = rng.uniform(0.001, 0.030)
        asym = rng.uniform(0.0, 1.0)
        t0, t1 = t, t + rtt
        est.sample(t0, t1, (t0 + asym * rtt) - true)
        assert abs(est.offset - true) <= est.uncertainty + 1e-12
        t = t1 + rng.uniform(0.0, 0.1)


def test_estimator_drifting_worker_widens_uncertainty():
    """A *moving* clock must report a wide bound, not a confident stale
    one: the drift term (|raw − smoothed|) feeds the uncertainty EWMA."""
    fixed = ClockOffsetEstimator()
    drifting = ClockOffsetEstimator()
    rtts = [0.002] * 40
    _simulate_rpcs(fixed, 2.0, rtts)
    final_true = _simulate_rpcs(drifting, 2.0, rtts, drift_per_rpc=0.005)
    assert drifting.uncertainty > fixed.uncertainty * 3
    # The smoothed estimate trails the moving truth, but stays within
    # the widened bound of the *recent* true offsets.
    assert abs(drifting.offset - final_true) < 0.25


def test_estimator_first_sample_and_backwards_clock():
    est = ClockOffsetEstimator()
    assert est.samples == 0 and math.isinf(est.uncertainty)
    assert est.to_dict()["uncertainty_s"] is None   # no samples yet
    est.sample(10.0, 9.0, 5.0)       # t1 < t0: skipped
    assert est.samples == 0
    est.sample(10.0, 10.004, 8.002)
    assert est.samples == 1
    assert est.offset == pytest.approx(10.002 - 8.002)
    assert est.uncertainty == pytest.approx(0.002)
    assert est.rebase(8.002) == pytest.approx(10.002)


def test_rebased_spans_never_reorder_causal_legs():
    """Causal order survives rebasing: the supervisor hands off at its
    T, the worker's decode leg starts (on the worker clock) strictly
    after receipt — after rebasing with a converged estimator the decode
    leg must not appear to start before the handoff ended, beyond the
    estimator's own uncertainty."""
    est = ClockOffsetEstimator()
    true = 7.75
    _simulate_rpcs(est, true, [0.002] * 10)
    handoff_end_local = 200.0
    # Worker-side decode starts 1ms after the handoff lands (worker clock).
    decode_start_remote = (handoff_end_local - true) + 0.001
    rebased = est.rebase(decode_start_remote)
    assert rebased >= handoff_end_local - est.uncertainty
    # And intra-worker ordering is preserved exactly (constant shift).
    remote_ts = [1.0, 1.5, 2.0, 2.25]
    rebased_ts = [est.rebase(ts) for ts in remote_ts]
    assert rebased_ts == sorted(rebased_ts)


# ----------------------------------------------------------------------
# TraceFederator
# ----------------------------------------------------------------------

def _span(name, ts_us, dur_us, *, pid=1, rid=None, trace=None, ph="X"):
    ev = {"ph": ph, "name": name, "cat": "test", "ts": float(ts_us),
          "pid": pid, "tid": 1}
    if ph == "X":
        ev["dur"] = float(dur_us)
    args = {}
    if rid:
        args["id"] = rid
    if trace:
        args["trace"] = trace
    if args:
        ev["args"] = args
    return ev


def test_federator_rebases_and_retags_pids():
    fed = TraceFederator()
    fed.source(0, pid=4242, label="worker0 gen1")
    # A converged 2s offset: worker spans land 2s later on our axis.
    fed.observe_rpc(0, 10.0, 10.002, 8.001)
    n = fed.ingest(0, [_span("request/decode", 1_000_000, 500, rid="r1")])
    assert n == 1 and len(fed) == 1
    ev = fed.events()[0]
    assert ev["ts"] == pytest.approx(1_000_000 + 2.0 * 1e6)
    assert ev["pid"] == TraceFederator.SYNTHETIC_PID_BASE + 0
    # Respawn: same key, new real pid — the render pid (Perfetto row)
    # stays stable; only the metadata label changes.
    fed.source(0, pid=5555, label="worker0 gen2")
    meta = fed.metadata_events()
    assert len(meta) == 1 and meta[0]["ph"] == "M"
    assert meta[0]["pid"] == TraceFederator.SYNTHETIC_PID_BASE + 0
    assert "worker0 gen2" in meta[0]["args"]["name"]
    assert "5555" in meta[0]["args"]["name"]


def test_federator_counts_unparented_and_dropped():
    from dlti_tpu.telemetry.distributed_trace import (
        federated_spans_total, unparented_spans_total,
    )

    fed = TraceFederator(capacity=4)
    base_fed = federated_spans_total.value
    base_unp = unparented_spans_total.value
    spans = [_span("engine/decode_prep", i * 100, 10) for i in range(6)]
    spans.append(_span("request/prefill", 999, 10, trace="t1"))
    fed.ingest(3, spans, remote_dropped=5)
    assert len(fed) == 4                       # ring bound holds
    assert fed.dropped_events == 3 + 5         # local evictions + remote
    assert federated_spans_total.value - base_fed == 7
    # Engine-step spans carry no request/trace linkage: unparented.
    assert unparented_spans_total.value - base_unp == 6


def test_federator_merged_dict_includes_local_and_offsets():
    fed = TraceFederator()
    fed.observe_rpc("w1", 0.0, 0.004, -2.998)  # worker ~3s behind
    fed.ingest("w1", [_span("request/decode", 0, 100, rid="r9")])
    local = SpanTracer(capacity=16, enabled=True)
    local.complete("gateway/queued", 0.0, 0.001, cat="gateway", id="r9")
    out = fed.merged_dict(local, local_label="supervisor")
    phs = [e["ph"] for e in out["traceEvents"]]
    assert phs.count("M") == 2                 # one per process
    names = {e["name"] for e in out["traceEvents"] if e["ph"] != "M"}
    assert {"gateway/queued", "request/decode"} <= names
    assert "w1" in out["clockOffsets"]
    assert out["clockOffsets"]["w1"]["offset_s"] == pytest.approx(
        3.0, abs=0.01)


# ----------------------------------------------------------------------
# Span-ring shipping cursor (SpanTracer.events_since)
# ----------------------------------------------------------------------

def test_events_since_walks_ring_without_duplicates():
    tr = SpanTracer(capacity=8, enabled=True)
    for i in range(5):
        tr.instant(f"request/submitted", id=f"r{i}")
    evs, dropped, cur = tr.events_since(0, limit=3)
    assert [e["args"]["id"] for e in evs] == ["r0", "r1", "r2"]
    assert dropped == 0
    evs2, dropped2, cur2 = tr.events_since(cur, limit=512)
    assert [e["args"]["id"] for e in evs2] == ["r3", "r4"]
    assert dropped2 == 0
    assert tr.events_since(cur2, limit=512) == ([], 0, cur2)


def test_events_since_reports_ring_evictions():
    tr = SpanTracer(capacity=4, enabled=True)
    for i in range(10):                        # 6 evicted before any ship
        tr.instant("request/submitted", id=f"r{i}")
    evs, dropped, cur = tr.events_since(0, limit=512)
    assert dropped == 6
    assert [e["args"]["id"] for e in evs] == ["r6", "r7", "r8", "r9"]
    assert cur == tr.total_events
    # A slow consumer that lags behind keeps honest accounting too.
    for i in range(10, 16):
        tr.instant("request/submitted", id=f"r{i}")
    evs, dropped, cur = tr.events_since(cur, limit=2)
    assert dropped == 2                        # r10, r11 already evicted
    assert [e["args"]["id"] for e in evs] == ["r12", "r13"]


# ----------------------------------------------------------------------
# Trace-context wire round trip
# ----------------------------------------------------------------------

def test_wire_round_trips_trace_id():
    req = Request(request_id="req-1", prompt_token_ids=[1, 2, 3],
                  params=SamplingParams(max_tokens=4),
                  trace_id=mint_trace_id())
    d = request_to_wire(req)
    assert d["trace_id"] == req.trace_id
    back = request_from_wire(d)
    assert back.trace_id == req.trace_id


def test_wire_old_frames_without_trace_id_still_parse():
    """A peer from before this change omits the field: the request
    arrives untraced ("") — never re-minted here, which would fork the
    id between processes."""
    req = Request(request_id="req-2", prompt_token_ids=[1],
                  params=SamplingParams(max_tokens=2))
    d = request_to_wire(req)
    d.pop("trace_id")
    back = request_from_wire(d)
    assert back.trace_id == ""


def test_mint_trace_id_is_unique_and_compact():
    ids = {mint_trace_id() for _ in range(256)}
    assert len(ids) == 256
    assert all(len(i) == 16 for i in ids)


# ----------------------------------------------------------------------
# Per-request timeline reconstruction
# ----------------------------------------------------------------------

def _request_events(rid="r1", trace="t-abc"):
    """A two-process request: gateway + queue on pid 1 (supervisor),
    prefill/decode on pid 100001 (worker, already rebased), with a
    kv_handoff overlapping the decode leg."""
    return [
        _span("gateway/queued", 0, 10_000, pid=1, rid=rid, trace=trace),
        _span("request/queued", 10_000, 5_000, pid=1, rid=rid, trace=trace),
        _span("request/prefill", 15_000, 30_000, pid=100001, rid=rid,
              trace=trace),
        _span("engine/kv_handoff", 45_000, 2_000, pid=1, rid=rid,
              trace=trace),
        _span("request/decode", 45_000, 55_000, pid=100001, rid=rid,
              trace=trace),
        # Unrelated request: must not leak into the timeline.
        _span("request/decode", 0, 99_000, pid=1, rid="other"),
    ]


def test_request_timeline_merges_across_processes():
    tl = request_timeline(_request_events(), "r1")
    assert tl["trace_id"] == "t-abc"           # picked up from the spans
    assert len(tl["spans"]) == 5
    assert tl["processes"] == [1, 100001]
    # Causally ordered by rebased start time.
    starts = [ev["ts"] for ev in tl["spans"]]
    assert starts == sorted(starts)
    assert tl["sequential_legs"] == list(SEQUENTIAL_LEGS)
    # Sequential legs tile the life: 10 + 5 + 30 + 55 ms; the handoff
    # overlaps decode and is reported but never summed.
    assert tl["sequential_sum_s"] == pytest.approx(0.100)
    assert "engine/kv_handoff" in tl["legs"]
    assert tl["wall_s"] == pytest.approx(0.100)
    assert tl["residual_s"] == pytest.approx(0.0, abs=1e-9)


def test_request_timeline_residual_vs_client_latency():
    tl = request_timeline(_request_events(), "r1", client_latency_s=0.112)
    assert tl["client_latency_s"] == pytest.approx(0.112)
    # 12ms the server never saw (client-side network / connect time).
    assert tl["residual_s"] == pytest.approx(0.012)


def test_request_timeline_joins_on_trace_id_alone():
    """Failover resubmits re-enter under a new engine request id but the
    SAME trace id: spans that only share args.trace still join."""
    events = _request_events(rid="r1", trace="t-abc")
    events.append(_span("request/decode", 110_000, 1_000, pid=100002,
                        rid="r1-retry1", trace="t-abc"))
    tl = request_timeline(events, "r1")
    assert len(tl["spans"]) == 6
    assert 100002 in tl["processes"]


def test_request_timeline_unions_mirror_and_worker_observations():
    """A fleet request is observed twice per leg — the supervisor mirror
    and the owning worker each emit request/prefill + request/decode for
    the same request. Leg durations are interval UNIONS, so the doubled
    observation must not double the sequential coverage."""
    rid, trace = "r1", "t-abc"
    events = [
        _span("gateway/queued", 0, 10_000, pid=1, rid=rid, trace=trace),
        # Supervisor mirror: prefill covers dispatch -> first token,
        # decode covers first token -> finish.
        _span("request/prefill", 10_000, 40_000, pid=1, rid=rid,
              trace=trace),
        _span("request/decode", 50_000, 50_000, pid=1, rid=rid,
              trace=trace),
        # Worker (rebased): queue leg inside the mirror's prefill
        # window, then near-identical prefill/decode observations.
        _span("request/queued", 11_000, 4_000, pid=100001, rid=rid,
              trace=trace),
        _span("request/prefill", 15_000, 34_000, pid=100001, rid=rid,
              trace=trace),
        _span("request/decode", 49_500, 50_000, pid=100001, rid=rid,
              trace=trace),
    ]
    tl = request_timeline(events, rid)
    assert tl["legs"]["request/prefill"]["count"] == 2
    assert tl["legs"]["request/prefill"]["pids"] == [1, 100001]
    # Union, not sum: mirror [10,50]ms dominates worker [15,49]ms.
    assert tl["legs"]["request/prefill"]["dur_s"] == pytest.approx(0.040)
    # Coverage = enqueue -> finish, despite 6 overlapping spans.
    assert tl["sequential_sum_s"] == pytest.approx(0.100)


def test_request_timeline_accepts_generator_input():
    tl = request_timeline(iter(_request_events()), "r1")
    assert len(tl["spans"]) == 5


# ----------------------------------------------------------------------
# Flight-dump merging (postmortem --all)
# ----------------------------------------------------------------------

def test_merge_dump_tails_rebases_onto_one_clock():
    sup = [_span("engine/kv_handoff", 5_000_000, 1_000, rid="r1")]
    # Worker clock 2s behind: its raw ts are 2s too small on our axis.
    wrk = [_span("request/decode", 3_000_500, 900, rid="r1")]
    out = merge_dump_tails([
        {"label": "supervisor flight-x", "pid": 100, "offset_s": 0.0,
         "uncertainty_s": None, "events": sup, "dropped": 0},
        {"label": "worker0 flight-y", "pid": 200, "offset_s": 2.0,
         "uncertainty_s": 0.0015, "events": wrk, "dropped": 3},
    ])
    evs = [e for e in out["traceEvents"] if e["ph"] != "M"]
    by_name = {e["name"]: e for e in evs}
    assert by_name["request/decode"]["ts"] == pytest.approx(5_000_500)
    assert by_name["request/decode"]["pid"] == 200
    # Sorted onto one axis: handoff (5.000s) precedes decode (5.0005s).
    assert [e["name"] for e in evs] == ["engine/kv_handoff",
                                       "request/decode"]
    meta = [e for e in out["traceEvents"] if e["ph"] == "M"]
    assert len(meta) == 2
    worker_meta = next(m for m in meta if "worker0" in m["args"]["name"])
    assert "±1.50ms" in worker_meta["args"]["name"]
    assert out["droppedEvents"] == 3
    assert {s["pid"] for s in out["sources"]} == {100, 200}


def test_merge_dump_tails_synthesizes_missing_pids():
    out = merge_dump_tails([
        {"label": "a", "events": [_span("request/decode", 0, 1, rid="r")]},
        {"label": "b", "events": [_span("request/prefill", 0, 1, rid="r")]},
    ])
    pids = {e["pid"] for e in out["traceEvents"]}
    assert len(pids) == 2
    assert all(p >= TraceFederator.SYNTHETIC_PID_BASE for p in pids)
