#!/usr/bin/env python
"""Host-overlap microbench (CPU-hermetic): quantify the host-latency-hiding
layer on both hot paths and emit one JSON artifact.

* **Training**: a tiny model trains twice over the same dataset — prefetch
  off (legacy inline fetch) vs on (``Config.data.prefetch_depth=2``) — with
  a synthetic per-batch host delay standing in for corpus-scale gather/pack
  cost. The metric is *host stall*: time the step thread blocked waiting
  for a batch (the ``train/batch_fetch`` tracer span). With prefetch on the
  gather overlaps the in-flight step, so the stall collapses toward zero.
* **Serving**: the engine decodes on its device-resident per-slot state
  (dirty tracking) and reports host-prep time per dispatch plus the upload
  counters, including a controlled steady-state window where the batch
  composition is fixed and a correct cache must issue ZERO uploads.

Run:  JAX_PLATFORMS=cpu python benchmarks_dev/host_overlap.py
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

    # Controlled steady-state window: one resident request, fixed batch
    # composition, one block per sequence — every dispatch is CLEAN and a
    # correct cache must upload nothing.
    eng2 = InferenceEngine(mc, params, ec)
    eng2.submit([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=40))
    eng2.step()  # admit + prefill
    eng2.step()  # first decode: uploads the admitted row
    up0 = eng2.stats["decode_state_uploads"]
    for _ in range(10):
        eng2.step()
    clean_window_uploads = eng2.stats["decode_state_uploads"] - up0

    # The account's prep phase with what it nests (plan, assemble, stage).
    acct = eng.telemetry.stepper
    prep_s = sum(v for k, v in acct.seconds().items()
                 if k in ("engine/decode_prep", "engine/decode_plan",
                          "engine/decode_assemble", "engine/decode_stage"))
    return {
        "decode_steps": eng.stats["decode_steps"],
        "generated_tokens": eng.stats["generated_tokens"],
        "decode_state_uploads": eng.stats["decode_state_uploads"],
        "decode_state_rows": eng.stats["decode_state_rows"],
        "decode_state_clean_syncs": eng.stats["decode_state_clean_syncs"],
        "clean_window_steps": 10,
        "clean_window_uploads": clean_window_uploads,
        "host_prep_mean_s": round(
            prep_s / max(1, acct.entries().get("engine/decode_prep", 0)), 6),
        "wall_s": round(wall, 4),
    }


def main() -> int:
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
        "serving": {"dirty_tracking": serve_on},
    }
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps(report))
    ok = (stall_on < stall_off
          and serve_on["clean_window_uploads"] == 0
          and train_on["final_loss"] == train_off["final_loss"])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
