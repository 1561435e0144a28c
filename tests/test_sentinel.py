"""Numeric fault tolerance (``dlti_tpu.training.sentinel``) — tier 1.

Three layers, mirroring the subsystem's own split:

* **Detector units** — spike-window math (cold start, re-arm), streak
  accounting, skip-list strike/quarantine semantics and persistence,
  SDC digest + majority attribution, chaos-spec parsing and injection.
* **Step-level** — the bf16 nonfinite gate: a NaN batch through the real
  compiled step must skip the optimizer update (params/opt state
  unchanged) while the step counter (and so the lr/rng schedule)
  advances — the fp16 scaler's skip semantics, extended.
* **Trainer-level** — a transient NaN skips and the run continues;
  with rollback armed, the run restores the last verified checkpoint
  and finishes with a loss trajectory bit-identical to a clean run; a
  pre-quarantined window is skipped by the data feed.

The serving guard (nonfinite decode output → replica quarantine) is
tested here too; the full CLI/gloo drills live in
``tests/test_sentinel_drill.py`` (slow tier).
"""

import json
import math
import threading

import numpy as np
import pytest

from dlti_tpu.config import (
    CheckpointConfig, Config, DataConfig, LoRAConfig, MODEL_PRESETS,
    OptimizerConfig, SentinelConfig, TrainConfig,
)
from dlti_tpu.training.chaos import TrainFaultInjector
from dlti_tpu.training.sentinel import (
    DataSkipList, NumericSentinel, SDC_EXIT_CODE, SpikeDetector,
    attribute_suspects, replicated_param_digest,
)

CFG = MODEL_PRESETS["llama_tiny"]


# ----------------------------------------------------------------------
# Spike detector
# ----------------------------------------------------------------------

def test_spike_detector_cold_start():
    d = SpikeDetector(window=8, min_samples=4, factor=2.0)
    # Nothing fires before min_samples normal readings — even wild values.
    assert not d.update(1.0)
    assert not d.update(100.0)  # admitted: no baseline to judge it by
    assert not d.update(1.0)
    assert not d.ready          # 3 admitted < min_samples=4
    assert not d.update(1.0)
    assert d.ready


def test_spike_detector_window_math_and_rearm():
    d = SpikeDetector(window=8, min_samples=4, factor=2.0)
    for v in (1.0, 1.1, 0.9, 1.0):
        assert not d.update(v)
    assert d.update(2.5)      # > 2 x median(~1.0): spike
    # Re-arm semantics: the spike was NOT admitted, so the baseline is
    # intact — a consecutive spike still fires, and a normal value does
    # not.
    assert d.update(2.5)
    assert not d.update(1.05)
    assert math.isclose(d.median, 1.0, abs_tol=0.1)


def test_spike_detector_min_delta_floors_noise():
    d = SpikeDetector(window=8, min_samples=2, factor=2.0, min_delta=1.0)
    for v in (0.01, 0.012, 0.011):
        d.update(v)
    # 3x the median but the absolute move is microscopic: not a spike.
    assert not d.update(0.03)


def test_spike_detector_ignores_nonfinite():
    d = SpikeDetector(window=4, min_samples=2, factor=2.0)
    d.update(1.0)
    d.update(1.0)
    assert not d.update(float("nan"))
    assert not d.update(float("inf"))
    assert d.median == 1.0  # nonfinite never entered the window


# ----------------------------------------------------------------------
# Sentinel streaks
# ----------------------------------------------------------------------

def test_numeric_sentinel_streak_and_rollback_due():
    s = NumericSentinel(SentinelConfig(rollback_after=2, min_samples=2,
                                       window=4))
    v = s.observe(1, float("nan"), 1.0, skipped_update=True)
    assert v["kind"] == "nonfinite" and not v["rollback_due"]
    v = s.observe(2, 1.0, float("inf"), skipped_update=True)
    assert v["kind"] == "nonfinite" and v["rollback_due"]
    assert v["streak"] == [(1, "nonfinite"), (2, "nonfinite")]
    # A clean step resets the streak.
    v = s.observe(3, 1.0, 1.0, skipped_update=False)
    assert v["kind"] == "" and not v["rollback_due"] and s.streak == []
    assert s.counts["nonfinite"] == 2
    assert s.counts["skipped_updates"] == 2
    s.note_rollback()
    assert s.rollbacks == 1
    assert "sentinel_rollbacks" in s.scalars()


def test_numeric_sentinel_rollback_budget():
    s = NumericSentinel(SentinelConfig(max_rollbacks=2))
    assert not s.over_budget()
    s.note_rollback()
    s.note_rollback()
    assert s.over_budget()


# ----------------------------------------------------------------------
# Skip-list
# ----------------------------------------------------------------------

def test_skiplist_strike_quarantine_and_roundtrip():
    sl = DataSkipList(quarantine_after=2)
    assert sl.strike([5, 7], step=10) == []         # first strike: replay
    assert sl.quarantined() == set()
    assert sl.strike([7], step=12) == [7]           # second strike: out
    assert sl.quarantined() == {7}
    meta = sl.to_meta()
    sl2 = DataSkipList(quarantine_after=2)
    sl2.merge_meta(meta)
    assert sl2.quarantined() == {7}
    assert sl2.windows[5]["strikes"] == 1
    # Merge keeps max strikes and sticky quarantine.
    sl2.merge_meta([{"pos": 5, "strikes": 0, "quarantined": False}])
    assert sl2.windows[5]["strikes"] == 1
    sl2.merge_meta([{"pos": 9, "quarantined": True}])
    assert 9 in sl2.quarantined()


def test_skiplist_file_persistence(tmp_path):
    sl = DataSkipList(quarantine_after=1)
    sl.strike([3], step=4)
    sl.save(str(tmp_path))
    raw = json.load(open(tmp_path / DataSkipList.FILENAME))
    assert raw["windows"][0]["pos"] == 3
    sl2 = DataSkipList(quarantine_after=1)
    sl2.load(str(tmp_path))
    assert sl2.quarantined() == {3}
    # A missing/corrupt file is a silent no-op (best-effort persistence).
    sl3 = DataSkipList()
    sl3.load(str(tmp_path / "nope"))
    (tmp_path / "bad" ).mkdir()
    (tmp_path / "bad" / DataSkipList.FILENAME).write_text("{not json")
    sl3.load(str(tmp_path / "bad"))
    assert len(sl3) == 0


# ----------------------------------------------------------------------
# SDC digest + attribution
# ----------------------------------------------------------------------

def test_attribute_suspects_majority_and_tiebreak():
    a, b = b"A" * 32, b"B" * 32
    assert attribute_suspects([a, a, a]) == []
    assert attribute_suspects([a, a, b]) == [2]
    assert attribute_suspects([b, a, a]) == [0]
    # 2-rank split: no majority — rank 0 is the reference, rank 1 the
    # suspect (the documented blind spot: a corrupt rank 0 in a 2-rank
    # world misattributes; 3+ ranks vote it out).
    assert attribute_suspects([a, b]) == [1]
    # All distinct: rank 0 stays the reference.
    assert attribute_suspects([a, b, b"C" * 32]) == [1, 2]
    assert attribute_suspects([]) == []


def test_replicated_param_digest_detects_bit_flip():
    import jax
    import jax.numpy as jnp

    tree = {"w": jnp.arange(8, dtype=jnp.float32),
            "b": jnp.ones((4,), jnp.float32)}
    d1, n1 = replicated_param_digest(tree)
    assert n1 == 2
    d2, _ = replicated_param_digest(
        jax.tree_util.tree_map(lambda x: x + 0, tree))
    assert d1 == d2  # value-identical trees hash identically
    host = np.array(tree["w"])
    host.view(np.uint32)[0] ^= 1  # one mantissa bit
    d3, _ = replicated_param_digest({"w": jnp.asarray(host),
                                     "b": tree["b"]})
    assert d3 != d1


# ----------------------------------------------------------------------
# Chaos injectors
# ----------------------------------------------------------------------

def test_chaos_spec_parsing_numeric_modes():
    inj = TrainFaultInjector.from_spec("4:nan-grad")
    assert (inj.step, inj.mode) == (4, "nan-grad")
    inj = TrainFaultInjector.from_spec("10:poison-batch")
    assert (inj.step, inj.mode) == (10, "poison-batch")
    inj = TrainFaultInjector.from_spec("3:param-flip:1")
    assert (inj.step, inj.mode, inj.rank) == (3, "param-flip", 1)
    assert TrainFaultInjector.from_spec("3:param-flip").rank == 1
    # host-kill stays supervisor-owned; a RANK field on other modes is a
    # spec error, not a silent drop.
    assert TrainFaultInjector.from_spec("3:host-kill:1") is None
    with pytest.raises(ValueError):
        TrainFaultInjector.from_spec("3:nan-grad:1")
    with pytest.raises(ValueError):
        TrainFaultInjector.from_spec("3:frob")


def test_chaos_nan_grad_fires_once_and_copies():
    inj = TrainFaultInjector.from_spec("4:nan-grad")
    batch = {"input_ids": np.ones((1, 2, 8), np.int32),
             "loss_mask": np.ones((1, 2, 8), np.int32)}
    assert inj.maybe_corrupt_batch(2, 3, batch) is None  # step 3 < 4
    out = inj.maybe_corrupt_batch(3, 4, batch)
    assert out is not None
    assert np.isnan(out["loss_mask"]).all()
    assert (batch["loss_mask"] == 1).all()  # original never mutated
    assert inj.maybe_corrupt_batch(4, 5, batch) is None  # fires once


def test_chaos_poison_batch_keyed_by_position_and_refires():
    inj = TrainFaultInjector.from_spec("7:poison-batch")
    ids = np.arange(16, dtype=np.int32).reshape(1, 2, 8)
    batch = {"input_ids": ids, "loss_mask": np.ones_like(ids)}
    assert inj.maybe_corrupt_batch(6, 7, batch) is None   # wrong position
    p1 = inj.maybe_corrupt_batch(7, 8, batch)
    p2 = inj.maybe_corrupt_batch(7, 12, batch)  # REPLAY: re-poisons,
    assert p1 is not None and p2 is not None    # deterministically
    assert (p1["input_ids"] == p2["input_ids"]).all()
    assert not (p1["input_ids"] == ids).all()
    assert sorted(p1["input_ids"].ravel()) == sorted(ids.ravel())
    assert (batch["input_ids"] == ids).all()  # original never mutated


def test_chaos_param_flip_rank_gated_single_process():
    import jax.numpy as jnp

    from dlti_tpu.training.state import TrainState

    class _S:
        params = {"w": jnp.ones((4,), jnp.float32)}

        def replace(self, **kw):
            out = _S()
            out.params = kw.get("params", self.params)
            return out

    # rank defaults to 1; this process is rank 0 -> no flip, but the
    # injector still retires (one corruption event per spec).
    inj = TrainFaultInjector.from_spec("2:param-flip")
    assert inj.maybe_corrupt_state(2, _S()) is None
    assert inj.fired
    inj0 = TrainFaultInjector.from_spec("2:param-flip:0")
    flipped = inj0.maybe_corrupt_state(2, _S())
    assert flipped is not None
    d_before, _ = replicated_param_digest(_S().params)
    d_after, _ = replicated_param_digest(flipped.params)
    assert d_before != d_after
    # One mantissa bit: the numeric delta is tiny, the digest delta total.
    assert np.allclose(np.array(flipped.params["w"]), 1.0, atol=1e-5)


# ----------------------------------------------------------------------
# Step-level: the bf16 nonfinite gate
# ----------------------------------------------------------------------

def test_bf16_step_skips_nonfinite_update():
    import jax
    import jax.numpy as jnp

    from dlti_tpu.models import LlamaForCausalLM
    from dlti_tpu.training import build_optimizer, create_train_state
    from dlti_tpu.training.step import make_train_step

    model = LlamaForCausalLM(CFG, None)
    tx = build_optimizer(OptimizerConfig(warmup_steps=1))
    state = create_train_state(jax.random.PRNGKey(0), model, tx, (2, 16),
                               lora_enabled=False)
    step = jax.jit(make_train_step(model, accum_steps=1))
    rng = jax.random.PRNGKey(1)
    ids = np.random.default_rng(0).integers(
        1, CFG.vocab_size, (1, 2, 16)).astype(np.int32)
    good = {"input_ids": ids, "loss_mask": np.ones_like(ids)}
    nan_mask = np.full(ids.shape, np.nan, np.float32)
    bad = {"input_ids": ids, "loss_mask": nan_mask}

    state1, m1 = step(state, good, jax.random.fold_in(rng, 1))
    assert float(m1["nonfinite"]) == 0.0
    assert float(m1["skipped_update"]) == 0.0

    before = jax.device_get(state1.params)
    opt_before = jax.device_get(state1.opt_state)
    state2, m2 = step(state1, bad, jax.random.fold_in(rng, 2))
    assert float(m2["nonfinite"]) == 1.0
    assert float(m2["skipped_update"]) == 1.0
    assert not math.isfinite(float(m2["loss"]))
    # The update was SKIPPED: params and optimizer state are bit-equal.
    after = jax.device_get(state2.params)
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        assert (np.asarray(a) == np.asarray(b)).all()
    for a, b in zip(jax.tree_util.tree_leaves(opt_before),
                    jax.tree_util.tree_leaves(jax.device_get(
                        state2.opt_state))):
        assert (np.asarray(a) == np.asarray(b)).all()
    # ...but the step counter advanced: the lr/rng schedule is a pure
    # function of the step index (skip is schedule-invariant).
    assert int(state2.step) == int(state1.step) + 1
    # And the next good step proceeds normally from the unpoisoned state.
    state3, m3 = step(state2, good, jax.random.fold_in(rng, 3))
    assert math.isfinite(float(m3["loss"]))
    assert float(m3["nonfinite"]) == 0.0


# ----------------------------------------------------------------------
# Trainer-level: skip, rollback, quarantine honoring
# ----------------------------------------------------------------------

def _train_cfg(tmp, fault="", sent=None, max_steps=8, step_log=""):
    from dlti_tpu.config import TelemetryConfig

    return Config(
        model=CFG, lora=LoRAConfig(r=2, alpha=4, dropout=0.0),
        optimizer=OptimizerConfig(warmup_steps=2),
        data=DataConfig(max_seq_len=32, prefetch_depth=0),
        checkpoint=CheckpointConfig(output_dir=str(tmp / "ck"),
                                    save_steps=2, save_total_limit=10),
        telemetry=TelemetryConfig(step_log_path=step_log),
        train=TrainConfig(num_epochs=1, max_steps=max_steps,
                          micro_batch_size=2, grad_accum_steps=1,
                          logging_steps=1000, fault_inject_step=fault,
                          sentinel=sent or SentinelConfig()),
    )


def _dataset():
    from dlti_tpu.data.pipeline import TokenBatchDataset

    rng = np.random.default_rng(0)
    seqs = [list(map(int, rng.integers(1, 500, 24))) for _ in range(32)]
    return TokenBatchDataset(sequences=seqs, seq_len=32, pad_id=0,
                             micro_batch_size=2, grad_accum_steps=1,
                             shuffle_seed=0, shard_by_host=False)


def _run(tmp, **kw):
    from dlti_tpu.training.trainer import Trainer

    t = Trainer(_train_cfg(tmp, **kw))
    state, rec = t.train(dataset=_dataset())
    return t, rec


@pytest.fixture(scope="module")
def clean_final_loss(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("clean")
    _, rec = _run(tmp)
    return rec.final_loss


def test_nan_grad_skips_update_and_steplog_records(tmp_path,
                                                  clean_final_loss):
    log = tmp_path / "steps.jsonl"
    t, rec = _run(tmp_path, fault="4:nan-grad", step_log=str(log))
    # Default rollback_after=3 > the single-step streak: no rollback —
    # the transient NaN cost one skipped update, nothing else.
    assert t._sentinel.rollbacks == 0
    assert t._sentinel.counts["nonfinite"] == 1
    assert t._sentinel.counts["skipped_updates"] == 1
    assert math.isfinite(rec.final_loss)
    rows = [json.loads(l) for l in open(log)]
    steps = {r["step"]: r for r in rows if r.get("type") == "step"}
    assert steps[4]["anomaly"] == "nonfinite"
    assert steps[4]["skipped_update"] == 1
    assert not math.isfinite(steps[4]["loss"])  # honest reporting
    assert steps[5]["anomaly"] == "" and steps[5]["skipped_update"] == 0
    assert steps[8]["rollbacks_total"] == 0


def test_nan_grad_rollback_matches_clean_run(tmp_path, clean_final_loss):
    t, rec = _run(tmp_path, fault="4:nan-grad",
                  sent=SentinelConfig(rollback_after=1))
    # One anomaly -> rollback to the verified step-2 checkpoint; the
    # replayed window is clean (transient fault), so the final loss is
    # BIT-IDENTICAL to a run that never faulted.
    assert t._sentinel.rollbacks == 1
    assert rec.final_loss == clean_final_loss
    # The implicated window got a strike but was NOT quarantined
    # (quarantine_after=2): transient faults replay.
    assert len(t._skiplist) == 1
    assert t._skiplist.quarantined() == set()
    # The skip-list persisted for crash recovery between saves.
    assert (tmp_path / "ck" / DataSkipList.FILENAME).exists()


def test_quarantined_window_is_skipped_on_resume(tmp_path):
    # Pre-seed the persistent skip-list (what a prior run's double
    # rollback would have written) and verify the data feed honors it:
    # the quarantined window never feeds a step, the feed moves on.
    ck = tmp_path / "ck"
    ck.mkdir()
    (ck / DataSkipList.FILENAME).write_text(json.dumps(
        {"format": 1, "windows": [{"pos": 2, "strikes": 2,
                                   "quarantined": True, "last_step": 9}]}))
    t, rec = _run(tmp_path, max_steps=6)
    assert t._live.get("sentinel_windows_skipped") == 1
    # All 6 steps executed (the feed substituted the next windows) and
    # the data cursor leads the step count by the skipped window.
    assert t._live["train_step"] == 6
    # Sidecar of the newest checkpoint carries the skip-list + cursor.
    from dlti_tpu.checkpoint import latest_verified_step, load_train_meta

    step = latest_verified_step(str(ck))
    meta = load_train_meta(str(ck), step)
    assert meta["data_pos"] == step + 1
    assert any(w["pos"] == 2 and w["quarantined"]
               for w in meta["skip_list"])


# ----------------------------------------------------------------------
# Watchdog rules
# ----------------------------------------------------------------------

def test_watchdog_sentinel_rules_fire_on_counter_growth():
    from dlti_tpu.config import WatchdogConfig
    from dlti_tpu.telemetry import AnomalyWatchdog, TimeSeriesSampler

    vals = {"sentinel_nonfinite_steps": 0, "sentinel_loss_spikes": 0,
            "sentinel_grad_spikes": 0, "sdc_mismatches": 0}
    sampler = TimeSeriesSampler(interval_s=60)
    sampler.add_source(lambda: dict(vals))
    wd = AnomalyWatchdog(WatchdogConfig(enabled=True), sampler)

    sampler.sample_now()
    assert wd.check_now() == []  # watermark init: no spurious alert
    vals["sentinel_nonfinite_steps"] = 2
    vals["sentinel_loss_spikes"] = 1
    sampler.sample_now()
    fired = wd.check_now()
    assert {a["rule"] for a in fired} == {"nonfinite_step", "loss_spike"}
    # Edge semantics: no growth -> no refire, and the rule re-arms.
    sampler.sample_now()
    assert wd.check_now() == []
    vals["sdc_mismatches"] = 1
    sampler.sample_now()
    assert {a["rule"] for a in wd.check_now()} == {"sdc_mismatch"}


# ----------------------------------------------------------------------
# Serving guard
# ----------------------------------------------------------------------

def _tiny_params():
    import jax
    import jax.numpy as jnp

    from dlti_tpu.models import LlamaForCausalLM

    model = LlamaForCausalLM(CFG, None)
    return model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]


def _nan_params(params):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda x: jnp.full_like(x, jnp.nan)
        if jnp.issubdtype(x.dtype, jnp.inexact) else x, params)


def test_engine_guard_trips_on_nan_params_before_streaming():
    from dlti_tpu.serving import (
        EngineConfig, InferenceEngine, NumericFault, SamplingParams,
    )

    ec = EngineConfig(max_seqs=2, block_size=8, num_blocks=32,
                      max_model_len=64, cache_dtype="float32",
                      eos_token_id=-1)
    eng = InferenceEngine(CFG, _tiny_params(), ec)
    req = eng.submit([1, 2, 3], SamplingParams(max_tokens=6,
                                               temperature=0.0))
    eng.step()  # prefill + first token
    eng.step()  # a decode step
    n_before = len(req.output_token_ids)
    assert n_before >= 1
    eng.executor.params = _nan_params(eng.executor.params)
    with pytest.raises(NumericFault):
        for _ in range(4):
            eng.step()
    # No garbage token was appended after the poison.
    assert len(req.output_token_ids) <= n_before + 1
    assert all(math.isfinite(lp) for lp in req.output_logprobs)
    assert eng.stats["numeric_faults"] >= 1


def test_engine_guard_trips_on_nan_prefill():
    from dlti_tpu.serving import (
        EngineConfig, InferenceEngine, NumericFault, SamplingParams,
    )

    ec = EngineConfig(max_seqs=2, block_size=8, num_blocks=32,
                      max_model_len=64, cache_dtype="float32",
                      eos_token_id=-1)
    eng = InferenceEngine(CFG, _nan_params(_tiny_params()), ec)
    req = eng.submit([1, 2, 3], SamplingParams(max_tokens=4))
    with pytest.raises(NumericFault):
        eng.step()
    assert req.output_token_ids == []  # the garbage first token never landed


def test_nan_logits_replica_quarantined_zero_client_errors():
    """Serving acceptance: nonfinite logits on one replica of a 2-replica
    gateway fleet -> that replica is quarantined, clients see zero
    errors, and every streamed token matches a clean single-engine
    reference (no garbage reached a user)."""
    import jax

    from dlti_tpu.config import GatewayConfig
    from dlti_tpu.data.tokenizer import IdTokenizer
    from dlti_tpu.serving import (
        EngineConfig, InferenceEngine, ReplicatedEngine, SamplingParams,
    )
    from dlti_tpu.serving.server import ServerConfig, make_server

    devices = jax.devices()
    if len(devices) < 2:
        devices = [devices[0], devices[0]]
    ec = EngineConfig(max_seqs=4, block_size=8, num_blocks=128,
                      max_model_len=128, cache_dtype="float32",
                      eos_token_id=-1)
    params = _tiny_params()
    # Replica 0's params go NaN at its 3rd step: the engine's numeric
    # guard (not a synthetic raise) must detect and fail it over.
    rep = ReplicatedEngine(CFG, params, ec, replicas=2, tensor=1,
                           devices=devices[:2], max_retries=2,
                           fault_inject_step="0:3:nan-logits")
    httpd, aeng = make_server(
        rep, IdTokenizer(vocab_size=CFG.vocab_size),
        ServerConfig(host="127.0.0.1", port=0, request_timeout_s=120,
                     default_params=SamplingParams(max_tokens=8),
                     gateway=GatewayConfig(enabled=True,
                                           max_queued_requests=64)))
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    port = httpd.server_address[1]

    import http.client

    def post(body):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = resp.read()
        conn.close()
        return resp.status, json.loads(data)

    try:
        prompts = [f"req {i}" for i in range(6)]
        results = [None] * len(prompts)

        def one(i):
            results[i] = post({"prompt": prompts[i], "max_tokens": 12,
                               "temperature": 0.0})

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(prompts))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)

        # Zero client-visible errors, full completions.
        for i, r in enumerate(results):
            assert r is not None and r[0] == 200, (i, r)
            assert r[1]["usage"]["completion_tokens"] == 12, r[1]

        # The poisoned replica was quarantined by the NUMERIC guard.
        assert rep.num_live == 1
        assert rep.failover["replica_faults"] == 1
        assert rep.stats["numeric_faults"] >= 1
        assert rep.failover["retries"] >= 1

        # No garbage tokens streamed: every completion is byte-identical
        # to a clean single-engine greedy reference.
        clean = InferenceEngine(CFG, params, ec)
        tok = IdTokenizer(vocab_size=CFG.vocab_size)
        for i, r in enumerate(results):
            ref = clean.generate([tok.encode(prompts[i], add_bos=True)],
                                 SamplingParams(max_tokens=12,
                                                temperature=0.0))[0]
            assert r[1]["choices"][0]["text"] == tok.decode(
                ref.output_token_ids), i
    finally:
        httpd.shutdown()
        if httpd.gateway is not None:
            httpd.gateway.shutdown()
        aeng.shutdown()
        httpd.server_close()


def test_replica_fault_spec_parsing():
    from dlti_tpu.serving.replicas import _parse_fault_inject

    assert _parse_fault_inject("") is None
    assert _parse_fault_inject("0:3") == (0, 3, "raise")
    assert _parse_fault_inject("1:5:nan-logits") == (1, 5, "nan-logits")
    with pytest.raises(ValueError):
        _parse_fault_inject("1:5:frob")


def test_sdc_exit_code_is_distinctive():
    from dlti_tpu.telemetry.watchdog import ABORT_EXIT_CODE

    assert SDC_EXIT_CODE not in (0, 1, 2, ABORT_EXIT_CODE)
    assert SDC_EXIT_CODE < 128  # clear of shell signal-death encodings
