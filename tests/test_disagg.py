"""Prefill/decode disaggregation (dlti_tpu.serving.disagg) — tier 1.

Layers, mirroring the subsystem's own structure:

* **Scheduler/executor split**: the engine's device half lives on
  :class:`EngineExecutor`; the engine proper is host scheduling alone —
  the unit contract the disagg controller builds on.
* **Paged-KV handoff**: block payloads fetched from a prefill engine and
  scattered into a decode engine are byte-equal on arrival, for bf16 AND
  int8 pools (scales travel with the payload).
* **Byte-identity**: completions with disaggregation on vs off are
  token-for-token identical — greedy and seeded-sampled, bf16 and int8
  KV — because the handoff carries the sampled first token and the
  origin slot's rng key bytes (fold_in stream continuity).
* **Failover drills**: killing a prefill-pool or decode-pool replica
  mid-run completes every request with zero client-visible errors.
* **Backpressure & shed**: staging queues respect handoff_queue_depth;
  a staged snapshot past handoff_deadline_s degrades to a decode-side
  re-prefill (counted, never an error).
* **Ledger pin**: the note_requeue fold — a second requeue before
  re-admission (preempt mid-chunked-prefill, then replica death) books
  BOTH windows instead of silently dropping the first.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlti_tpu.config import MODEL_PRESETS
from dlti_tpu.models import LlamaForCausalLM
from dlti_tpu.serving import (
    DisaggController, EngineConfig, InferenceEngine, SamplingParams,
)
from dlti_tpu.serving.engine import Request
from dlti_tpu.serving.executor import EngineExecutor
from dlti_tpu.telemetry.ledger import note_readmitted, note_requeue

CFG = MODEL_PRESETS["llama_tiny"]


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


PROMPTS = [[1, 2, 3, 4, 5], [6, 7, 8], [9, 10, 11, 12], [13, 14]]


# ----------------------------------------------------------------------
# Scheduler/executor split
# ----------------------------------------------------------------------

def test_executor_owns_device_half_and_engine_holds_none_of_it(tiny_params):
    eng = InferenceEngine(CFG, tiny_params, _ec())
    assert isinstance(eng.executor, EngineExecutor)
    # One owner: what replica NaN-poisoning rebinds on the executor is
    # what the programs and the memledger's owner read — the engine keeps
    # no second handle on any of it, by any of its old names.
    for name in ("params", "cache", "model", "adapter_pool", "_device",
                 "_decode_fn", "_prefill_fns", "_spec_fn", "_sample_fn", "_fold_keys", "_aot_or_jit",
                 "_fetch_block_kv", "_restore_block", "_state_cache"):
        assert not hasattr(eng, name), name
    marker = jax.tree_util.tree_map(lambda x: x, eng.executor.params)
    eng.executor.params = marker
    assert eng.memledger._owners["params"]() is marker
    # The program calls and the block transport the handoff rides live on
    # the executor class alone.
    for name in ("prefill", "stage_decode", "launch_decode", "stage_spec",
                 "launch_spec", "fetch", "fetch_block_kv", "restore_block"):
        assert name in EngineExecutor.__dict__
        assert name not in InferenceEngine.__dict__


def test_prefill_only_engine_never_decodes(tiny_params):
    eng = InferenceEngine(CFG, tiny_params, _ec())
    eng.prefill_only = True
    req = eng.submit([1, 2, 3, 4], SamplingParams(max_tokens=8))
    for _ in range(20):
        eng.step()
    # Prefill ran (first token sampled), decode never did: the slot sits
    # harvestable with exactly one output token.
    assert req.output_token_ids and len(req.output_token_ids) == 1
    slot = next(s for s in eng.slots if s.request is req)
    assert not slot.prefilling and slot.last_token is not None
    assert eng.has_work  # still occupied: backpressure, not completion


# ----------------------------------------------------------------------
# Paged-KV handoff byte-equality
# ----------------------------------------------------------------------

def _prefill_and_export(src, prompt, params):
    req = src.submit(prompt, params)
    for _ in range(50):
        src.step()
        slot = next((s for s in src.slots if s.request is req), None)
        if slot is not None and not slot.prefilling \
                and slot.last_token is not None:
            break
    else:
        pytest.fail("prefill never completed")
    snap = src.export_handoff(slot)
    assert snap is not None
    return req, snap


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_handoff_blocks_byte_equal_after_restore(tiny_params, kv_dtype):
    ec = _ec(cache_dtype=kv_dtype)
    src = InferenceEngine(CFG, tiny_params, ec)
    dst = InferenceEngine(CFG, tiny_params, ec)
    src.prefill_only = True
    prompt = list(range(3, 3 + 21))  # 21 tokens -> 3 blocks at block 8
    req, snap = _prefill_and_export(
        src, prompt, SamplingParams(max_tokens=4))
    assert len(snap["payloads"]) == 3
    if kv_dtype == "int8":
        # Scales must travel with the int8 payload.
        layer0 = next(iter(snap["payloads"][0].values()))
        assert any("scale" in k for k in layer0)
    assert dst.adopt_handoff(snap)
    slot = next(s for s in dst.slots if s.request is req)
    for got, sent in zip((dst.executor.fetch_block_kv(b) for b in slot.blocks),
                         snap["payloads"]):
        assert got is not None
        assert set(got) == set(sent)
        for lk in got:
            assert set(got[lk]) == set(sent[lk])
            for ak in got[lk]:
                np.testing.assert_array_equal(
                    np.asarray(got[lk][ak]), np.asarray(sent[lk][ak]))


def test_handoff_preserves_rng_key_and_counts(tiny_params):
    src = InferenceEngine(CFG, tiny_params, _ec())
    src.prefill_only = True
    req, snap = _prefill_and_export(
        src, [5, 6, 7], SamplingParams(max_tokens=4, temperature=0.8))
    assert snap["gen_count"] == 1
    assert snap["last_token"] == req.output_token_ids[0]
    dst = InferenceEngine(CFG, tiny_params, _ec())
    assert dst.adopt_handoff(snap)
    slot = next(s for s in dst.slots if s.request is req)
    np.testing.assert_array_equal(dst._slot_keys[slot.slot_id],
                                  snap["slot_key"])
    assert int(dst._gen_counts[slot.slot_id]) == 1


# ----------------------------------------------------------------------
# Byte-identity: disaggregation on vs off
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("sp", [
    SamplingParams(max_tokens=8, temperature=0.0),              # greedy
    SamplingParams(max_tokens=8, temperature=0.9, seed=7),      # sampled
], ids=["greedy", "seeded-sampled"])
def test_outputs_identical_disagg_on_vs_off(tiny_params, devices,
                                            kv_dtype, sp):
    ec = _ec(cache_dtype=kv_dtype)
    base = InferenceEngine(CFG, tiny_params, ec)
    expect = [r.output_token_ids for r in base.generate(PROMPTS, sp)]
    ctl = DisaggController(CFG, tiny_params, ec, prefill_replicas=1,
                           decode_replicas=2, devices=devices[:3])
    got = [r.output_token_ids for r in ctl.generate(PROMPTS, sp)]
    assert got == expect
    assert ctl.handoff["completed"] >= len(PROMPTS)


# ----------------------------------------------------------------------
# Kill drills: either pool loses a replica, zero client errors
# ----------------------------------------------------------------------

def _assert_all_completed(results, n):
    assert len(results) == n
    bad = [r for r in results if r.finish_reason not in ("stop", "length")]
    assert not bad, [f"{r.request_id}:{r.finish_reason}" for r in bad]


def test_prefill_replica_kill_drill(tiny_params, devices):
    # Step 1: a prefill engine drains its whole admission in one step
    # (short prompts), so the injected fault must land on the replica's
    # first worked step to hit it mid-flight.
    ctl = DisaggController(CFG, tiny_params, _ec(), prefill_replicas=2,
                           decode_replicas=1, devices=devices[:3],
                           fault_inject_step="prefill:0:1")
    res = ctl.generate(PROMPTS * 2, SamplingParams(max_tokens=8))
    _assert_all_completed(res, len(PROMPTS) * 2)
    assert ctl.prefill.num_live == 1
    assert ctl.failover["replica_faults"] == 1


def test_decode_replica_kill_drill(tiny_params, devices):
    ctl = DisaggController(CFG, tiny_params, _ec(), prefill_replicas=1,
                           decode_replicas=2, devices=devices[:3],
                           fault_inject_step="decode:0:3")
    res = ctl.generate(PROMPTS * 2, SamplingParams(max_tokens=8))
    _assert_all_completed(res, len(PROMPTS) * 2)
    assert ctl.decode.num_live == 1
    assert ctl.failover["replica_faults"] == 1


def test_whole_prefill_pool_dead_degrades_to_colocated(tiny_params,
                                                       devices):
    ctl = DisaggController(CFG, tiny_params, _ec(), prefill_replicas=1,
                           decode_replicas=1, devices=devices[:2],
                           fault_inject_step="prefill:0:1")
    res = ctl.generate(PROMPTS, SamplingParams(max_tokens=8))
    _assert_all_completed(res, len(PROMPTS))
    assert ctl.prefill.num_live == 0  # decode pool carried the rest


# ----------------------------------------------------------------------
# Backpressure & deadline shed
# ----------------------------------------------------------------------

def test_staging_respects_queue_depth(tiny_params, devices):
    ctl = DisaggController(CFG, tiny_params,
                           _ec(max_seqs=2, num_blocks=32),
                           prefill_replicas=1, decode_replicas=1,
                           devices=devices[:2], handoff_queue_depth=1)
    reqs = [ctl.submit(p, SamplingParams(max_tokens=16))
            for p in PROMPTS + PROMPTS]
    cap = ctl.handoff_queue_depth * len(ctl.decode.engines)
    for _ in range(600):
        if not ctl.has_work:
            break
        ctl.step()
        assert sum(len(q) for q in ctl._staging) <= cap
    assert not ctl.has_work
    assert all(r.finish_reason in ("stop", "length") for r in reqs)


def test_handoff_deadline_sheds_to_reprefill(tiny_params, devices):
    # Decode pool with 2 slots, 8 competing requests: staged snapshots
    # wait, the tiny deadline trips, and the shed path re-prefills on the
    # decode replica — latency, never an error.
    ctl = DisaggController(CFG, tiny_params,
                           _ec(max_seqs=2, num_blocks=32),
                           prefill_replicas=1, decode_replicas=1,
                           devices=devices[:2], handoff_deadline_s=1e-4)
    res = ctl.generate(PROMPTS + PROMPTS, SamplingParams(max_tokens=16))
    _assert_all_completed(res, len(PROMPTS) * 2)
    assert ctl.handoff["sheds"] > 0


def test_concurrent_mode_completes_everything(tiny_params, devices):
    # The production serve path: prefill pool on its own thread. Not a
    # byte-identity test (scheduling is timing-dependent) — a liveness
    # and zero-error drill.
    ctl = DisaggController(CFG, tiny_params, _ec(), prefill_replicas=1,
                           decode_replicas=1, devices=devices[:2])
    ctl.start()
    try:
        reqs = [ctl.submit(p, SamplingParams(max_tokens=8))
                for p in PROMPTS * 3]
        deadline = time.monotonic() + 60
        while ctl.has_work and time.monotonic() < deadline:
            ctl.step()
    finally:
        ctl.stop()
    assert all(r.finish_reason in ("stop", "length") for r in reqs)


# ----------------------------------------------------------------------
# Phase accounting
# ----------------------------------------------------------------------

def test_handoff_books_as_kv_handoff_phase(tiny_params, devices):
    from dlti_tpu.telemetry.ledger import request_breakdown

    ctl = DisaggController(CFG, tiny_params, _ec(), prefill_replicas=1,
                           decode_replicas=1, devices=devices[:2])
    req = ctl.submit([1, 2, 3, 4, 5], SamplingParams(max_tokens=8))
    while ctl.has_work:
        ctl.step()
    assert req.finish_reason in ("stop", "length")
    assert "kv_handoff" in req.stall_s
    phases = request_breakdown(req)
    assert phases.get("kv_handoff", 0.0) >= 0.0
    assert ctl.handoff["completed"] == 1


def test_handoff_span_carries_trace_context(tiny_params, devices):
    """Distributed-trace survival across the disagg staging path: the
    request's trace_id (minted at submit) rides into the
    engine/kv_handoff span, and the per-request timeline shows the
    staging leg between prefill and decode."""
    from dlti_tpu.telemetry import get_tracer
    from dlti_tpu.telemetry.distributed_trace import request_timeline

    tracer = get_tracer()
    prev = tracer.enabled
    tracer.enabled = True
    try:
        ctl = DisaggController(CFG, tiny_params, _ec(), prefill_replicas=1,
                               decode_replicas=1, devices=devices[:2])
        req = ctl.submit([1, 2, 3, 4, 5], SamplingParams(max_tokens=8))
        assert len(req.trace_id) == 16
        while ctl.has_work:
            ctl.step()
        assert req.finish_reason in ("stop", "length")
        spans = [ev for ev in tracer.events()
                 if ev.get("name") == "engine/kv_handoff"
                 and (ev.get("args") or {}).get("id") == req.request_id]
        assert spans, "staging must emit the kv_handoff span"
        assert all(s["args"].get("trace") == req.trace_id for s in spans)
        tl = request_timeline(tracer.events(), req.request_id)
        assert tl["trace_id"] == req.trace_id
        assert {"engine/kv_handoff", "request/prefill",
                "request/decode"} <= set(tl["legs"]), sorted(tl["legs"])
        # The staging window overlaps the lifecycle legs: reported but
        # never counted toward the sequential coverage.
        assert "engine/kv_handoff" not in tl["sequential_legs"]
    finally:
        tracer.enabled = prev


def test_note_requeue_folds_open_mark_instead_of_dropping_it():
    """The mid-chunked-prefill double-requeue bug: a slot preempted
    mid-prompt has an open "preempt" mark; its replica then dies and
    note_requeue("failover") fires BEFORE any re-admission closed the
    window. The old overwrite dropped the preempt wait (it silently
    rebooked into prefill); the fold must keep both windows and
    accumulate stall_prefill_s across re-admissions."""
    req = Request(request_id="r", prompt_token_ids=[1, 2, 3],
                  params=SamplingParams())
    note_requeue(req, "preempt")
    time.sleep(0.012)
    note_requeue(req, "failover")  # second requeue, mark still open
    time.sleep(0.012)
    note_readmitted(req)
    assert req.stall_s.get("preempt", 0.0) >= 0.01
    assert req.stall_s.get("failover", 0.0) >= 0.01
    # No first token yet -> both windows charge the prefill-side stall.
    assert req.stall_prefill_s >= req.stall_s["preempt"] + \
        req.stall_s["failover"] - 1e-6


# ----------------------------------------------------------------------
# Metrics & registry exposition
# ----------------------------------------------------------------------

def test_registry_exposes_pool_and_handoff_metrics(tiny_params, devices):
    import types

    from dlti_tpu.serving.server import build_registry

    ctl = DisaggController(CFG, tiny_params, _ec(), prefill_replicas=1,
                           decode_replicas=1, devices=devices[:2])
    registry = build_registry(types.SimpleNamespace(engine=ctl))
    names = registry.metric_names()
    from dlti_tpu.serving.disagg import (
        KV_HANDOFF_METRIC_NAMES, POOL_METRIC_NAMES,
    )

    ctl.generate([[1, 2, 3]], SamplingParams(max_tokens=4))
    exposition = registry.render_prometheus()
    for name in POOL_METRIC_NAMES + KV_HANDOFF_METRIC_NAMES:
        assert name in exposition, name
    assert "dlti_kv_handoff_seconds" in names


def test_stats_surface_aggregates_pools(tiny_params, devices):
    ctl = DisaggController(CFG, tiny_params, _ec(), prefill_replicas=1,
                           decode_replicas=1, devices=devices[:2])
    ctl.generate(PROMPTS, SamplingParams(max_tokens=4))
    s = ctl.stats
    # Admission counts once, on the prefill pool; the decode-side
    # adoption (like resubmit) does not double count.
    assert s["requests"] == len(PROMPTS)
    assert set(s["pools"]) == {"prefill", "decode"}
    assert s["kv_handoff"]["completed"] == len(PROMPTS)
    # Handoff staging is a pinned memory-ledger owner on decode engines.
    for eng in ctl.decode.engines:
        assert "kv_handoff_staging" in eng.memledger.owners()
