#!/usr/bin/env python
"""Host-overlap microbench (CPU-hermetic): quantify the host-latency-hiding
layer on both hot paths and emit one JSON artifact.

* **Training**: a tiny model trains twice over the same dataset — prefetch
  off (legacy inline fetch) vs on (``Config.data.prefetch_depth=2``) — with
  a synthetic per-batch host delay standing in for corpus-scale gather/pack
  cost. The metric is *host stall*: time the step thread blocked waiting
  for a batch (the ``train/batch_fetch`` tracer span). With prefetch on the
  gather overlaps the in-flight step, so the stall collapses toward zero.
* **Serving**: the engine stages every decode round as one packed upload
  and one program call, and reports host-prep time per dispatch plus the
  two counters that say so (each equals the rounds launched).
* **Staging drill** (``--staging``, for the chip's host): what a round's
  staging costs by the number of transfers and program calls it makes,
  alone and beside 32 busy threads (see the section below).

Run:  JAX_PLATFORMS=cpu python benchmarks_dev/host_overlap.py
      chiprun -- python benchmarks_dev/host_overlap.py --staging chiprun_out/staging.json
Artifact: results/host_overlap_cpu.json (path override: first CLI arg).
Wired into `pytest -m slow` as a smoke: tests/test_host_overlap_bench.py.
"""

from __future__ import annotations

import json
import os
import sys
import time

_repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _repo)

if "--staging" not in sys.argv:  # the staging drill runs on the chip's host
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

GATHER_DELAY_S = 0.008   # synthetic per-batch host gather/pack cost
TRAIN_STEPS = 12
DECODE_TOKENS = 48


def _make_dataset(delay_s: float):
    from dlti_tpu.data import TokenBatchDataset

    rng = np.random.default_rng(0)
    seqs = [list(map(int, rng.integers(1, 500, size=24)))
            for _ in range(4 * (TRAIN_STEPS + 4))]
    ds = TokenBatchDataset(sequences=seqs, seq_len=32, pad_id=0,
                           micro_batch_size=4, grad_accum_steps=1)

    class SlowGather:
        """Proxy adding a fixed host delay per batch — the stand-in for
        corpus-scale gather/pack/stack cost on the step thread."""

        def steps_per_epoch(self):
            return ds.steps_per_epoch()

        def epoch(self, epoch_idx=0, skip_steps=0):
            for b in ds.epoch(epoch_idx, skip_steps):
                time.sleep(delay_s)
                yield b

    return SlowGather()


def bench_training(prefetch_depth: int) -> dict:
    from dlti_tpu.config import (
        CheckpointConfig, Config, DataConfig, LoRAConfig, MODEL_PRESETS,
        OptimizerConfig, ParallelConfig, TrainConfig,
    )
    from dlti_tpu.telemetry import configure_tracer
    from dlti_tpu.training.trainer import Trainer

    cfg = Config(
        model=MODEL_PRESETS["llama_tiny"],
        lora=LoRAConfig(r=2, alpha=4, dropout=0.0),
        optimizer=OptimizerConfig(warmup_steps=2),
        parallel=ParallelConfig(),
        data=DataConfig(max_seq_len=32, prefetch_depth=prefetch_depth),
        train=TrainConfig(num_epochs=1, max_steps=TRAIN_STEPS,
                          micro_batch_size=4, grad_accum_steps=1,
                          logging_steps=1000, metrics_csv=os.devnull),
        checkpoint=CheckpointConfig(save_strategy="no"),
    )
    tracer = configure_tracer(enabled=True)
    tracer.clear()
    trainer = Trainer(cfg)
    t0 = time.perf_counter()
    _, record = trainer.train(dataset=_make_dataset(GATHER_DELAY_S))
    wall = time.perf_counter() - t0
    # Chrome-trace events: dur is microseconds.
    stall_us = sum(e.get("dur", 0) for e in tracer.events()
                   if e.get("name") == "train/batch_fetch")
    configure_tracer(enabled=False)
    return {
        "prefetch_depth": prefetch_depth,
        "steps": TRAIN_STEPS,
        "synthetic_gather_delay_s": GATHER_DELAY_S,
        "host_stall_s": round(stall_us / 1e6, 6),
        "wall_s": round(wall, 4),
        "final_loss": round(float(record.final_loss), 6),
    }


def bench_serving() -> dict:
    import jax
    import jax.numpy as jnp

    from dlti_tpu.config import MODEL_PRESETS
    from dlti_tpu.models import LlamaForCausalLM
    from dlti_tpu.serving import EngineConfig, InferenceEngine, SamplingParams

    mc = MODEL_PRESETS["llama_tiny"]
    model = LlamaForCausalLM(mc, None)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    ec = EngineConfig(max_seqs=4, block_size=64, num_blocks=16,
                      max_model_len=64, cache_dtype="float32",
                      eos_token_id=-1)
    eng = InferenceEngine(mc, params, ec)
    prompts = [[1, 2, 3, 4], [5, 6, 7], [8, 9, 10, 11], [12, 13]]
    sp = SamplingParams(temperature=0.0, max_tokens=DECODE_TOKENS)
    t0 = time.perf_counter()
    eng.generate(prompts, sp)
    wall = time.perf_counter() - t0

    # The account's prep phase with what it nests (plan, assemble, stage).
    acct = eng.telemetry.stepper
    prep_s = sum(v for k, v in acct.seconds().items()
                 if k in ("engine/decode_prep", "engine/decode_plan",
                          "engine/decode_assemble", "engine/decode_stage"))
    return {
        "decode_steps": eng.stats["decode_steps"],
        "generated_tokens": eng.stats["generated_tokens"],
        "decode_host_uploads": eng.stats["decode_host_uploads"],
        "decode_program_calls": eng.stats["decode_program_calls"],
        "host_prep_mean_s": round(
            prep_s / max(1, acct.entries().get("engine/decode_prep", 0)), 6),
        "wall_s": round(wall, 4),
    }


# ---------------------------------------------------------------------------
# The staging drill (``--staging``): what the host pays to stage and launch one
# plain decode round, by the number of transfers and program calls it makes.
# Run it where the answer matters (the chip's host: ``chiprun -- python
# benchmarks_dev/host_overlap.py --staging``); on the CPU backend it only
# rehearses. Self-contained on purpose: the ``per_field`` arm is the staging
# the engine had until PR 45 (ten small uploads, a row updater, the decode
# call, a count bump), kept here as the thing to compare a staging against.
# ---------------------------------------------------------------------------
STAGING_SLOTS = 32
STAGING_BLOCKS = 544        # max_blocks_per_seq of the widest serving cell
STAGING_ROUNDS = 300
STAGING_PERIOD_S = 0.020    # a round of a closed loop: the wait releases the GIL
HANDLERS = 32
HANDLER_CPU_S = 0.0004      # handler_cpu_us_per_token ~ 400 (PERF.md, PR 44)


STAGING_CACHE_ITEMS = 1 << 22   # the stand-in cache: 2048 x 2048 float32
STAGING_BUSY_MATMULS = 120      # ~10 ms of a v5e: a decode step's length


def _staging_programs(S: int, packing, matmuls: int = 0):
    """Stand-ins for the decode programs with the real calling conventions
    (a donated cache first, the tokens of the round before, then the
    per-slot state): the drill times the host. ``matmuls`` = 0: next to no
    device work (the device idle when a round is staged); else that many
    2048-wide products a call, so that the round before is still on the
    device when the next is staged, as in the engine's loop."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    def draw(cache, ids, pos, bt, keys, cnt, temp, top_k, top_p, extra):
        rng = jax.vmap(jax.random.fold_in)(keys, cnt)
        u = jax.vmap(lambda k: jax.random.uniform(k, ()))(rng)
        tok = (ids[:, 0] + pos[:, 0] + bt.sum(axis=1) + top_k + extra
               + (u * temp * top_p * 7).astype(jnp.int32))
        if matmuls:
            x = cache.reshape(2048, 2048).astype(jnp.bfloat16)
            y = jax.lax.fori_loop(0, matmuls,
                                  lambda _, y: jnp.tanh(y @ x), x)
            cache = cache + y[0, 0].astype(jnp.float32)
        return cache + 1, tok, u

    @partial(jax.jit, donate_argnums=(0,))
    def decode_fields(cache, prev, ids, pos, bt, keys, cnt, temp, top_k,
                      top_p, extra):
        ids = jnp.where(ids < 0, prev[:S, None], ids)
        return draw(cache, ids, pos, bt, keys, cnt, temp, top_k, top_p, extra)

    @partial(jax.jit, donate_argnums=(0,))
    def decode_packed(cache, prev, packed):
        ids, *state = packing.unpack(packed)   # as the engine's programs do
        ids = jnp.where(ids < 0, prev[:S, None], ids)
        return draw(cache, ids, *state)

    apply_rows = jax.jit(
        lambda dev, idx, rows: tuple(a.at[idx].set(r)
                                     for a, r in zip(dev, rows)))
    bump = jax.jit(lambda cnt, k: cnt + k)
    return decode_fields, decode_packed, apply_rows, bump


def _handler_threads(n: int):
    """``n`` threads that each do what a streaming handler does an event:
    wake on a queue, build JSON frames until ~HANDLER_CPU_S of interpreter
    time is spent. Returns ``(wake, stop)``."""
    import queue
    import threading

    frame = {"id": "cmpl-0123456789", "object": "text_completion",
             "choices": [{"index": 0, "text": " token", "logprobs": None,
                          "finish_reason": None}]}
    t0 = time.perf_counter()
    for _ in range(200):
        json.dumps(frame).encode()
    per = (time.perf_counter() - t0) / 200
    reps = max(1, int(HANDLER_CPU_S / per))
    queues = [queue.Queue() for _ in range(n)]

    def handler(q):
        while q.get() is not None:
            for _ in range(reps):
                json.dumps(frame).encode()

    threads = [threading.Thread(target=handler, args=(q,), daemon=True)
               for q in queues]
    for t in threads:
        t.start()

    def wake():
        for q in queues:
            q.put(1)

    def stop():
        for q in queues:
            q.put(None)
        for t in threads:
            t.join()

    return wake, stop


def bench_staging(rounds: int = STAGING_ROUNDS, matmuls: int = 0) -> dict:
    import jax
    import jax.numpy as jnp

    from dlti_tpu.serving.decode_state import RoundPacking

    S, MB = STAGING_SLOTS, STAGING_BLOCKS
    dev = jax.devices()[0]
    packing = RoundPacking(S, MB, "adapter_ids")
    decode_fields, decode_packed, apply_rows, bump = _staging_programs(
        S, packing, matmuls)
    rng = np.random.default_rng(0)
    host = {
        "block_tables": rng.integers(0, 4000, (S, MB)).astype(np.int32),
        "slot_keys": rng.integers(0, 2**32, (S, 2), dtype=np.uint64
                                  ).astype(np.uint32),
        "gen_counts": rng.integers(0, 200, (S,)).astype(np.int32),
        "temperature": np.full((S,), 0.8, np.float32),
        "top_k": np.zeros((S,), np.int32),
        "top_p": np.ones((S,), np.float32),
        "adapter_ids": np.arange(S, dtype=np.int32)}
    fields = tuple(host)

    def new_cache():
        return jax.device_put(
            np.full((STAGING_CACHE_ITEMS,), 1e-3, np.float32), dev)

    no_prev = jax.device_put(np.zeros((S,), np.int32), dev)
    ids = np.full((S, 1), -1, np.int32)
    pos = np.full((S, 1), 100, np.int32)

    def per_field():
        """Ten uploads and three program calls: two dirty rows a round."""
        state = {"cache": new_cache(), "prev": no_prev,
                 "dev": tuple(jax.device_put(host[f], dev) for f in fields)}

        def stage():
            idx = np.array([3, 17], np.int32)
            rows = tuple(jnp.asarray(np.ascontiguousarray(host[f][idx]))
                         for f in fields)
            state["dev"] = apply_rows(state["dev"], jnp.asarray(idx), rows)
            return (jnp.asarray(ids), jnp.asarray(pos), *state["dev"])

        def launch(staged):
            state["cache"], tok, lp = decode_fields(
                state["cache"], state["prev"], *staged)
            d = list(state["dev"])
            d[2] = bump(d[2], np.int32(1))
            state["dev"] = tuple(d)
            state["prev"] = tok
            return tok, lp

        return stage, launch, 10, 3

    def packed(put):
        state = {"cache": new_cache(), "prev": no_prev}

        def stage():
            return put(packing.pack(ids, pos, host))

        def launch(staged):
            state["cache"], tok, lp = decode_packed(
                state["cache"], state["prev"], staged)
            state["prev"] = tok
            return tok, lp

        return stage, launch, 1, 1

    arms = {
        "per_field": per_field,
        "packed_device_put": lambda: packed(lambda x: jax.device_put(x, dev)),
        "packed_asarray": lambda: packed(jnp.asarray),
    }

    def run(arm, wake) -> dict:
        stage, launch, uploads, calls = arms[arm]()
        pending = None
        t_stage, t_launch = [], []
        for i in range(rounds + 20):
            t0 = time.perf_counter()
            staged = stage()
            t1 = time.perf_counter()
            out = launch(staged)
            t2 = time.perf_counter()
            if i >= 20:  # the first rounds compile and settle
                t_stage.append(t1 - t0)
                t_launch.append(t2 - t1)
            if pending is not None:      # the loop a round ahead: fetch the
                jax.device_get(pending)  # round before, this one queued
            pending = out
            if wake is not None:
                wake()
                rest = STAGING_PERIOD_S - (time.perf_counter() - t0)
                if rest > 0:
                    time.sleep(rest)
        jax.device_get(pending)

        def ms(xs, q):
            return round(1e3 * float(np.quantile(xs, q)), 4)

        both = np.add(t_stage, t_launch)
        return {"uploads_a_round": uploads, "program_calls_a_round": calls,
                "stage_ms_mean": round(1e3 * float(np.mean(t_stage)), 4),
                "stage_ms_p50": ms(t_stage, 0.5),
                "launch_ms_mean": round(1e3 * float(np.mean(t_launch)), 4),
                "launch_ms_p50": ms(t_launch, 0.5),
                "round_ms_mean": round(1e3 * float(np.mean(both)), 4),
                "round_ms_p50": ms(both, 0.5), "round_ms_p90": ms(both, 0.9)}

    report = {"benchmark": "decode_round_staging",
              "device": {"platform": dev.platform, "kind": dev.device_kind},
              "slots": S, "packed_shape": [S, packing.width],
              "packed_bytes": S * packing.width * 4, "rounds": rounds,
              "device_matmuls_a_round": matmuls,
              "handlers": HANDLERS, "handler_cpu_s_an_event": HANDLER_CPU_S,
              "period_s_beside_handlers": STAGING_PERIOD_S}
    # Alone twice (first, and again after the busy arm): order effects show.
    report["alone"] = {a: run(a, None) for a in arms}
    wake, stop = _handler_threads(HANDLERS)
    try:
        report["beside_handlers"] = {a: run(a, wake) for a in arms}
        report["beside_handlers_again"] = {
            a: run(a, wake) for a in reversed(list(arms))}
    finally:
        stop()
    report["alone_again"] = {a: run(a, None) for a in arms}
    if matmuls:
        report["transfer_sweep"] = _transfer_sweep(
            dev, decode_packed, packing, new_cache(), no_prev,
            packing.pack(ids, pos, host))
    return report


def _transfer_sweep(dev, decode_packed, packing, cache, prev, packed) -> dict:
    """What one ``device_put`` costs the caller by its size, with the device
    idle and with a program of ~10 ms just launched: ms until the call
    returns, and until the array is on the device."""
    import jax

    out = {}
    for kb in (1, 8, 32, 70, 280):
        x = np.zeros((kb * 256,), np.int32)
        for busy in (False, True):
            ret, done = [], []
            for _ in range(40):
                if busy:
                    cache, prev, _ = decode_packed(
                        cache, prev, jax.device_put(packed, dev))
                t0 = time.perf_counter()
                y = jax.device_put(x, dev)
                t1 = time.perf_counter()
                y.block_until_ready()
                t2 = time.perf_counter()
                jax.block_until_ready(prev)
                ret.append(t1 - t0)
                done.append(t2 - t0)
            out[f"{kb}KB_{'busy' if busy else 'idle'}"] = {
                "returns_ms_p50": round(1e3 * float(np.median(ret[5:])), 4),
                "on_device_ms_p50": round(1e3 * float(np.median(done[5:])), 4)}
    return out


def main_staging(argv) -> int:
    out_path = next((a for a in argv if not a.startswith("--")), None)
    report = bench_staging()
    # And again with the round before still on the device at every staging.
    report["device_busy"] = bench_staging(matmuls=STAGING_BUSY_MATMULS)
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
    print(json.dumps(report))
    return 0


def main() -> int:
    if "--staging" in sys.argv:
        return main_staging(sys.argv[1:])
    out_path = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        _repo, "results", "host_overlap_cpu.json")
    train_off = bench_training(prefetch_depth=0)
    train_on = bench_training(prefetch_depth=2)
    serve_on = bench_serving()
    stall_off, stall_on = train_off["host_stall_s"], train_on["host_stall_s"]
    report = {
        "benchmark": "host_overlap_cpu",
        "platform": os.environ.get("JAX_PLATFORMS", "cpu"),
        "train": {
            "prefetch_off": train_off,
            "prefetch_on": train_on,
            "stall_reduction": round(1.0 - stall_on / stall_off, 4)
            if stall_off > 0 else 0.0,
        },
        "serving": {"packed_rounds": serve_on},
    }
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps(report))
    ok = (stall_on < stall_off
          and serve_on["decode_host_uploads"] == serve_on["decode_steps"]
          and serve_on["decode_program_calls"] == serve_on["decode_steps"]
          and train_on["final_loss"] == train_off["final_loss"])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
