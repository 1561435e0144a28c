"""HBM memory ledger: per-owner attribution, conservation, OOM
forensics, and headroom-aware admission (tiny model, CPU).

The acceptance bar for the ledger is *conservation*: every snapshot's
bucket map (owners + untracked + residual) sums to bytes-in-use exactly
— on a synthetic tree, on a live Trainer, and on a live server where
``/debug/memory`` and ``/metrics`` must tell the same story. The
consumers ride along: an injected ``hbm-squeeze`` OOM in training and a
RESOURCE_EXHAUSTED in the engine both leave a flight dump whose
``memory.json`` says where the memory went (and postmortem renders it),
and the engine defers admission under headroom pressure instead of
faulting — zero client-visible errors, proved with a chaos balloon.
"""

import http.client
import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlti_tpu.config import (
    CheckpointConfig, Config, DataConfig, FlightRecorderConfig, LoRAConfig,
    MODEL_PRESETS, TelemetryConfig, TrainConfig, WatchdogConfig,
)
from dlti_tpu.data.tokenizer import ByteTokenizer
from dlti_tpu.models import LlamaForCausalLM
from dlti_tpu.serving import EngineConfig, InferenceEngine, SamplingParams
from dlti_tpu.serving.server import ServerConfig, make_server
from dlti_tpu.telemetry import memledger as ml
from dlti_tpu.telemetry.flightrecorder import (
    FlightRecorder, install as install_recorder, list_dumps, load_dump,
)
from dlti_tpu.telemetry.memledger import (
    MemoryBalloon, MemoryLedger, is_oom_error, tree_nbytes,
)
from dlti_tpu.telemetry.tracer import SpanTracer, configure_tracer, get_tracer
from dlti_tpu.training.chaos import SimulatedOOM, TrainFault

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))
import memory_plan  # noqa: E402

CFG = MODEL_PRESETS["llama_tiny"]


def _assert_conserved(snap):
    """The ledger's core contract: buckets sum to bytes_in_use EXACTLY."""
    assert sum(snap["buckets"].values()) == snap["bytes_in_use"], \
        snap["buckets"]


# ----------------------------------------------------------------------
# Unit: attribution arithmetic on a synthetic tree
# ----------------------------------------------------------------------

def test_conservation_with_owners_untracked_and_carve():
    ledger = MemoryLedger()
    a = jax.block_until_ready(jnp.zeros((256, 64), jnp.float32))
    b = jax.block_until_ready(jnp.ones((128,), jnp.float32))
    stray = jax.block_until_ready(jnp.zeros((99,), jnp.float32))

    ledger.register("params", {"w": a, "b": b})
    snap = ledger.snapshot(top_k=4)
    assert snap["source"] in ("device", "live_arrays")
    assert snap["owners"]["params"]["bytes"] == int(a.nbytes) + int(b.nbytes)
    # The stray array is live but unowned -> untracked, never lost.
    assert snap["untracked_bytes"] >= int(stray.nbytes)
    _assert_conserved(snap)
    assert snap["num_live_arrays"] >= 3
    # top_k surfaces the largest unowned arrays with shape/dtype.
    assert all({"shape", "dtype", "nbytes", "per_device"} <= set(e)
               for e in snap["top_untracked_arrays"])

    # A carve moves bytes out of its parent without touching the total.
    ledger.register_carve("prefix_cache_hbm", "params", lambda: int(b.nbytes))
    snap2 = ledger.snapshot()
    assert snap2["owners"]["prefix_cache_hbm"]["bytes"] == int(b.nbytes)
    assert snap2["owners"]["prefix_cache_hbm"]["carved_from"] == "params"
    assert snap2["owners"]["params"]["bytes"] == int(a.nbytes)
    _assert_conserved(snap2)

    # An array registered under two owners is counted once (aliasing).
    ledger.register("optimizer_state", [a])
    snap3 = ledger.snapshot()
    assert snap3["owners"]["optimizer_state"]["bytes"] == 0
    _assert_conserved(snap3)


def test_disabled_ledger_is_inert():
    ledger = MemoryLedger(enabled=False)
    ledger.register("params", jnp.zeros((8,)))
    assert ledger.snapshot() == {}
    assert ledger.scalars() == {}
    assert ledger.to_dict() == {}
    assert ledger.headroom_bytes() is None


def test_headroom_and_peak_tracking():
    ledger = MemoryLedger()
    arr = jax.block_until_ready(jnp.zeros((1024,), jnp.float32))
    ledger.register("params", [arr])
    snap = ledger.snapshot()
    # CPU without a budget: capacity unknown -> headroom None (gating
    # consumers must skip, not treat as 0).
    if snap["source"] == "live_arrays":
        assert snap["headroom_bytes"] is None
    cap = snap["bytes_in_use"] + (8 << 20)
    ledger.set_capacity(cap)
    snap2 = ledger.snapshot()
    assert 0 < snap2["headroom_bytes"] <= cap
    assert snap2["peak_bytes"] >= snap["bytes_in_use"]
    s = ledger.scalars()
    assert s["hbm_headroom_bytes"] > 0
    assert 0.0 <= s["hbm_headroom_frac"] <= 1.0


def test_balloon_inflate_registers_and_deflate_releases():
    ledger = MemoryLedger()
    balloon = MemoryBalloon(ledger=ledger)
    balloon.inflate(1 << 20)
    assert balloon.nbytes >= 1 << 20
    snap = ledger.snapshot()
    assert snap["owners"]["chaos_balloon"]["bytes"] >= 1 << 20
    _assert_conserved(snap)
    balloon.deflate()
    assert balloon.nbytes == 0
    # Owner entry released with the bytes.
    assert "chaos_balloon" not in ledger.snapshot()["owners"]


def test_is_oom_error_classification():
    assert is_oom_error(MemoryError())
    assert is_oom_error(RuntimeError("RESOURCE_EXHAUSTED: out of memory"))
    assert is_oom_error(SimulatedOOM("RESOURCE_EXHAUSTED: injected"))
    assert not is_oom_error(ValueError("bad shape"))
    assert not is_oom_error(RuntimeError("device disconnected"))


# ----------------------------------------------------------------------
# Watchdog: hbm_pressure rule
# ----------------------------------------------------------------------

def test_watchdog_hbm_pressure_rule():
    from dlti_tpu.telemetry import AnomalyWatchdog, TimeSeriesSampler

    cell = {"hbm_headroom_frac": 0.5}
    sampler = TimeSeriesSampler(interval_s=60.0)
    sampler.add_source(lambda: dict(cell))
    wd = AnomalyWatchdog(
        WatchdogConfig(enabled=True, hbm_headroom_floor_frac=0.1), sampler)
    sampler.sample_now()
    assert [a for a in wd.check_now() if a["rule"] == "hbm_pressure"] == []
    cell["hbm_headroom_frac"] = 0.04   # below the 10% floor
    sampler.sample_now()
    fired = [a for a in wd.check_now() if a["rule"] == "hbm_pressure"]
    assert len(fired) == 1
    assert "headroom" in fired[0]["message"]
    # Edge-triggered; recovery re-arms.
    sampler.sample_now()
    assert [a for a in wd.check_now() if a["rule"] == "hbm_pressure"] == []
    cell["hbm_headroom_frac"] = 0.6
    sampler.sample_now()
    wd.check_now()
    cell["hbm_headroom_frac"] = 0.02
    sampler.sample_now()
    assert [a for a in wd.check_now() if a["rule"] == "hbm_pressure"]


# ----------------------------------------------------------------------
# Live engine + server: attribution, /debug/memory vs /metrics
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_params():
    model = LlamaForCausalLM(CFG, None)
    rng = jax.random.PRNGKey(0)
    return model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]


@pytest.fixture(scope="module")
def engine(tiny_params):
    ec = EngineConfig(max_seqs=4, block_size=8, num_blocks=64,
                      max_model_len=64, cache_dtype="float32",
                      eos_token_id=-1, admit_min_headroom_frac=0.25)
    return InferenceEngine(CFG, tiny_params, ec)


@pytest.fixture(scope="module")
def live_server(tiny_params):
    ec = EngineConfig(max_seqs=4, block_size=8, num_blocks=128,
                      max_model_len=128, cache_dtype="float32",
                      eos_token_id=-1)
    eng = InferenceEngine(CFG, tiny_params, ec)
    httpd, async_engine = make_server(
        eng, ByteTokenizer(),
        ServerConfig(host="127.0.0.1", port=0,
                     default_params=SamplingParams(max_tokens=8)))
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield "127.0.0.1", port, eng
    httpd.shutdown()
    async_engine.shutdown()
    httpd.server_close()


def _get(host, port, path):
    conn = http.client.HTTPConnection(host, port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def test_engine_ledger_owners_and_conservation(engine):
    assert engine.memledger.enabled
    snap = engine.memledger.snapshot()
    assert snap["owners"]["params"]["bytes"] > 0
    assert snap["owners"]["kv_block_pool"]["bytes"] > 0
    _assert_conserved(snap)
    # A decode pass doesn't break conservation (state churn, temp
    # arrays, donation all land in a bucket).
    r = engine.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                                max_tokens=4))
    while engine.has_work:
        engine.step()
    assert r.done and len(r.output_token_ids) == 4
    _assert_conserved(engine.memledger.snapshot())


def test_server_debug_memory_and_metrics_agree(live_server):
    host, port, eng = live_server
    # Drive one real completion so the pools are exercised.
    conn = http.client.HTTPConnection(host, port, timeout=120)
    conn.request("POST", "/v1/completions",
                 json.dumps({"prompt": "hi", "max_tokens": 4,
                             "temperature": 0.0}),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200, resp.read()
    resp.read()
    conn.close()

    st, raw = _get(host, port, "/debug/memory")
    assert st == 200
    snap = json.loads(raw)
    _assert_conserved(snap)
    assert snap["owners"]["params"]["bytes"] > 0
    assert snap["owners"]["kv_block_pool"]["bytes"] > 0
    assert "ts" in snap

    # /metrics must tell the same story: refresh the gauges through the
    # same scalars() path the server's sampler runs, then compare the
    # stable owner (params never churns between the two scrapes).
    eng.memledger.scalars()
    st, raw = _get(host, port, "/metrics")
    assert st == 200
    text = raw.decode()
    samples = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name, value = line.split()
        samples[name] = float(value)
    assert samples['dlti_hbm_bytes{owner="params"}'] == \
        snap["owners"]["params"]["bytes"]
    assert samples['dlti_hbm_bytes{owner="kv_block_pool"}'] == \
        snap["owners"]["kv_block_pool"]["bytes"]
    assert "dlti_hbm_peak_bytes" in samples
    assert "dlti_hbm_untracked_bytes" in samples
    assert samples["dlti_hbm_peak_bytes"] >= snap["owners"]["params"]["bytes"]


def test_server_debug_memory_404_when_disabled(tiny_params):
    ec = EngineConfig(max_seqs=2, block_size=8, num_blocks=32,
                      max_model_len=32, cache_dtype="float32",
                      eos_token_id=-1, memory_ledger=False)
    eng = InferenceEngine(CFG, tiny_params, ec)
    assert not eng.memledger.enabled
    httpd, async_engine = make_server(
        eng, ByteTokenizer(), ServerConfig(host="127.0.0.1", port=0))
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        st, _ = _get("127.0.0.1", port, "/debug/memory")
        assert st == 404
    finally:
        httpd.shutdown()
        async_engine.shutdown()
        httpd.server_close()


# ----------------------------------------------------------------------
# Headroom-aware admission: defer, don't fault (chaos hbm-squeeze)
# ----------------------------------------------------------------------

def test_squeeze_defers_admission_with_zero_client_errors(engine):
    ledger = engine.memledger
    balloon = MemoryBalloon(ledger=ledger)
    balloon_bytes = 8 << 20
    try:
        # Gate off while capacity is unknown: requests flow normally.
        r1 = engine.submit([5, 6, 7], SamplingParams(temperature=0.0,
                                                     max_tokens=3))
        while engine.has_work:
            engine.step()
        assert r1.done and r1.finish_reason == "length"
        assert engine.stats.get("hbm_deferred_admissions", 0) == 0

        # Squeeze: balloon + a capacity placed so that headroom is below
        # 25% of capacity while inflated and above it once deflated, for
        # ANY base usage (cap in [(4/3)base, (4/3)(base+B))).
        base = ledger.snapshot()["bytes_in_use"]
        balloon.inflate(balloon_bytes)
        ledger.set_capacity((4 * base + 2 * balloon_bytes) // 3)

        r2 = engine.submit([1, 2, 3, 4], SamplingParams(temperature=0.0,
                                                        max_tokens=3))
        for _ in range(4):
            engine.step()
        # Deferred: still queued, never admitted, never errored.
        assert not r2.done
        assert engine.num_active == 0
        deferred = engine.stats["hbm_deferred_admissions"]
        assert deferred >= 4

        # Pressure relieved -> the queued request completes normally.
        # The degraded mode was latency, never a client-visible error.
        balloon.deflate()
        while engine.has_work:
            engine.step()
        assert r2.done and r2.finish_reason == "length"
        assert len(r2.output_token_ids) == 3
    finally:
        balloon.deflate()
        ledger.set_capacity(0)  # leave the module fixture un-gated


# ----------------------------------------------------------------------
# OOM forensics: engine dump (reason="oom" + memory.json)
# ----------------------------------------------------------------------

def test_engine_oom_leaves_memory_dump(engine, tmp_path, monkeypatch):
    rec = FlightRecorder(str(tmp_path), tracer=SpanTracer())
    rec.add_memory_source(engine.memledger.to_dict)
    install_recorder(rec)
    try:
        def boom():
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: out of memory while allocating "
                "decode buffers")
        monkeypatch.setattr(engine, "_admit", boom)
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            engine.step()
    finally:
        install_recorder(None)
    dumps = list_dumps(str(tmp_path))
    assert len(dumps) == 1
    data = load_dump(dumps[0])
    assert data["context.json"]["reason"] == "oom"
    assert data["context.json"]["where"] == "engine_step"
    mem = data["memory.json"]
    assert mem["owners"]["params"]["bytes"] > 0
    assert sum(mem["buckets"].values()) == mem["bytes_in_use"]


# ----------------------------------------------------------------------
# Live Trainer: steplog fields, conservation, hbm-squeeze OOM drill
# ----------------------------------------------------------------------

def _train_batches(n=6):
    rng = np.random.default_rng(0)
    ids = [rng.integers(1, 500, (1, 2, 16), dtype=np.int32)
           for _ in range(n)]
    return [{"input_ids": a, "labels": a} for a in ids]


def _train_cfg(tmp, max_steps, fault="", budget=0, flight_dir=""):
    return Config(
        model=CFG, lora=LoRAConfig(enabled=False),
        data=DataConfig(max_seq_len=16),
        checkpoint=CheckpointConfig(save_strategy="no"),
        train=TrainConfig(num_epochs=1, micro_batch_size=2,
                          grad_accum_steps=1, max_steps=max_steps,
                          logging_steps=100, fault_inject_step=fault),
        telemetry=TelemetryConfig(
            step_log_path=str(tmp / "steps.jsonl"),
            hbm_budget_bytes=budget,
            flight_recorder=FlightRecorderConfig(dir=flight_dir)),
    )


def test_trainer_steplog_hbm_fields_and_conservation(tmp_path):
    from dlti_tpu.training import Trainer

    budget = 1 << 40  # 1 TiB: guaranteed headroom on a CI host
    trainer = Trainer(_train_cfg(tmp_path, max_steps=2, budget=budget))
    trainer.train(batches_per_epoch=_train_batches())

    rows = [json.loads(line) for line in open(tmp_path / "steps.jsonl")]
    steps = [r for r in rows if r.get("type") == "step"]
    assert len(steps) == 2
    for r in steps:
        assert r["hbm_bytes_in_use"] > 0
        assert 0 < r["hbm_headroom_bytes"] <= budget

    # The run's ledger still holds the final state: owners attributed,
    # buckets conserved on the live training process.
    snap = trainer._memledger.snapshot()
    assert snap["owners"]["params"]["bytes"] > 0
    assert snap["owners"]["optimizer_state"]["bytes"] > 0
    _assert_conserved(snap)
    # train() uninstalled the process-wide ledger on the way out.
    assert ml.get_ledger() is not trainer._memledger


def test_trainer_steplog_headroom_sentinel_without_budget(tmp_path):
    from dlti_tpu.training import Trainer

    Trainer(_train_cfg(tmp_path, max_steps=1)).train(
        batches_per_epoch=_train_batches())
    rows = [json.loads(line) for line in open(tmp_path / "steps.jsonl")]
    steps = [r for r in rows if r.get("type") == "step"]
    # CPU, no budget: capacity unknown -> -1 sentinel, never a fake 0.
    assert steps[0]["hbm_headroom_bytes"] == -1
    assert steps[0]["hbm_bytes_in_use"] > 0


def test_training_hbm_squeeze_dump_and_postmortem(tmp_path, monkeypatch):
    from dlti_tpu.training import Trainer

    monkeypatch.setenv("DLTI_CHAOS_BALLOON_BYTES", str(4 << 20))
    flight = tmp_path / "flight"
    cfg = _train_cfg(tmp_path, max_steps=4, fault="2:hbm-squeeze",
                     flight_dir=str(flight))
    try:
        with pytest.raises(TrainFault, match="RESOURCE_EXHAUSTED"):
            Trainer(cfg).train(batches_per_epoch=_train_batches())
    finally:
        configure_tracer(enabled=False)
        get_tracer().clear()

    dumps = list_dumps(str(flight))
    assert dumps, "hbm-squeeze left no flight dump"
    data = load_dump(dumps[-1])
    assert data["context.json"]["reason"] == "chaos_hbm-squeeze"
    mem = data["memory.json"]
    # The balloon was still live at dump time: the black box names the
    # squeezer and conserves the total.
    assert mem["owners"]["chaos_balloon"]["bytes"] >= 4 << 20
    assert mem["owners"]["params"]["bytes"] > 0
    assert sum(mem["buckets"].values()) == mem["bytes_in_use"]

    # postmortem renders "where the memory went" from the same dump.
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "postmortem.py"),
         dumps[-1]],
        capture_output=True, text=True, cwd=REPO, timeout=60)
    assert r.returncode == 0, r.stderr[-1000:]
    assert "where the memory went" in r.stdout
    assert "chaos_balloon" in r.stdout
    rj = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "postmortem.py"),
         dumps[-1], "--json"],
        capture_output=True, text=True, cwd=REPO, timeout=60)
    assert rj.returncode == 0, rj.stderr[-1000:]
    summary = json.loads(rj.stdout)
    assert summary["memory"]["buckets"]
    assert summary["memory"]["buckets"]["chaos_balloon"] >= 4 << 20
    assert summary["memory"]["source"] in ("device", "live_arrays")


# ----------------------------------------------------------------------
# Planner vs measured: scripts/memory_plan.py cross-check
# ----------------------------------------------------------------------

def test_memory_plan_training_matches_measured_params(tiny_params):
    plan = memory_plan.plan_training(CFG, param_dtype="float32")
    measured = tree_nbytes(tiny_params)
    # The analytic count tracks the real init to within 10% on the tiny
    # preset (norm scales et al. are the only unmodeled leaves).
    assert abs(plan["owners"]["params"] - measured) / measured < 0.10
    assert plan["owners"]["optimizer_state"] == 2 * plan["trainable_params"] * 4
    # A budget verdict that can't be wrong by construction.
    p2 = memory_plan.plan_training(CFG, param_dtype="float32",
                                   budget_bytes=plan["total_bytes"] + 1)
    assert p2["fits"] and p2["headroom_bytes"] == 1


def test_memory_plan_serving_matches_measured_kv_pool(engine):
    ec = engine.cfg
    plan = memory_plan.plan_serving(
        CFG, param_dtype="float32", kv_dtype="float32",
        num_blocks=ec.num_blocks, block_size=ec.block_size,
        max_model_len=ec.max_model_len)
    snap = engine.memledger.snapshot()
    measured_pool = snap["owners"]["kv_block_pool"]["bytes"] + \
        snap["owners"].get("prefix_cache_hbm", {}).get("bytes", 0)
    # The engine pre-allocates exactly the planned pool (fp32: payload
    # only, no quantization scales).
    assert plan["owners"]["kv_block_pool"] == measured_pool
    assert plan["kv_bytes_per_token"] == \
        2 * CFG.num_layers * CFG.num_kv_heads * CFG.resolved_head_dim * 4
    assert plan["max_resident_tokens"] == (ec.num_blocks - 1) * ec.block_size


@pytest.mark.parametrize("width,held", [(48, 48), (1024, 1024),
                                        (1856, 1920)])
def test_memory_plan_counts_what_the_held_experts_hold(width, held):
    """Parameters reported are the published count; the bytes held are of
    the experts at their held width (nemotron3_nano_30b's 1,856: 1,920)."""
    import dataclasses

    from dlti_tpu.config import MODEL_PRESETS

    cfg = dataclasses.replace(MODEL_PRESETS["nemotron_h_tiny"],
                              moe_intermediate_size=width)
    pads = (cfg.layer_pattern.count("E") * cfg.moe_held * 2
            * cfg.hidden_size * (held - width))
    assert cfg.held_pad_params == pads
    for plan in (memory_plan.plan_training(cfg, param_dtype="bfloat16"),
                 memory_plan.plan_serving(cfg, param_dtype="bfloat16")):
        assert plan["num_params"] == cfg.num_params()
        assert plan["owners"]["params"] == 2 * (cfg.num_params() + pads)
    assert MODEL_PRESETS["llama_tiny"].held_pad_params == 0


def test_memory_plan_lora_trainable_count():
    n = memory_plan.lora_trainable_params(CFG, r=2)
    h, hd = CFG.hidden_size, CFG.resolved_head_dim
    q = 2 * (h + CFG.num_heads * hd)
    kv = 2 * (h + CFG.num_kv_heads * hd)
    o = 2 * (CFG.num_heads * hd + h)
    assert n == CFG.num_layers * (q + 2 * kv + o)


def test_memory_plan_cli_renders(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "memory_plan.py"),
         "--model", "llama_tiny", "--serving", "--num-blocks", "64",
         "--block-size", "8", "--kv-dtype", "float32",
         "--budget-gb", "1", "--json"],
        capture_output=True, text=True, cwd=REPO, timeout=60)
    assert r.returncode == 0, r.stderr[-1000:]
    p = json.loads(r.stdout)
    assert p["mode"] == "serving" and p["fits"]
    assert p["owners"]["kv_block_pool"] > 0
    assert p["max_blocks_in_budget"] >= 64
