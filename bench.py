"""Training-step benchmark: one configuration, one JSON line.

Runs a bare LoRA-SFT train step (no Trainer loop, no data pipeline) of one
model configuration on the local chip and prints ONE JSON line as the last
line of stdout:

    {"metric": ..., "value": N, "unit": "tok/s/chip", "vs_baseline": N,
     "platform": "tpu", "device_kind": ..., "device_count": N, ...}

Exit code 0 only when that line holds a measurement. Any failure — a bad
setting, no backend, an out-of-memory, a compile error — prints the same
line with ``"value": 0.0`` and an ``"error"`` and exits non-zero. A CPU
backend is a failure too: a timing of XLA:CPU is not a device metric, so
there is no fallback to it.

The configuration is Llama-2-7B + LoRA r=16 on q/k/v/o, micro-batch 4,
seq 512, bf16 base, remat — the one cell the driver has a record of.
``vs_baseline`` divides by the reference's only recorded training
throughput (ZeRO-2, same model, one V100-SXM2-32GB, ~2.93 it/s at micro-bs
1 x seq 512, i.e. ~1500 tok/s; BASELINE.md).

Env overrides: BENCH_MODEL (a preset name; ``DLTI_MODEL_LAYERS=N`` keeps its
first N layers, ``dlti_tpu.config.resolve_model``), BENCH_BS, BENCH_SEQ,
BENCH_STEPS, BENCH_QUANT ("" | "int8"), BENCH_REMAT (a remat policy or
"none"), BENCH_SYNC (optimizer steps per compiled call).
"""

from __future__ import annotations

import json
import os
import sys
import time

# Source checkout wins over any installed copy; an installed dlti-tpu
# serves scripts run from outside a checkout.
_repo_root = os.path.dirname(os.path.abspath(__file__))
if os.path.isdir(os.path.join(_repo_root, "dlti_tpu")):
    sys.path.insert(0, _repo_root)
del _repo_root

METRIC = "lora_sft_tokens_per_sec_per_chip"
V100_BASELINE_TOK_S = 2.93 * 512  # ~1500 tok/s (BASELINE.md)


class BenchConfigError(ValueError):
    """A setting the benchmark cannot run (exit code 2)."""


def _settings() -> dict:
    env = os.environ
    s = dict(model=env.get("BENCH_MODEL", "llama2_7b"),
             bs=int(env.get("BENCH_BS", 4)),
             seq=int(env.get("BENCH_SEQ", 512)),
             steps=int(env.get("BENCH_STEPS", 10)),
             quant=env.get("BENCH_QUANT", ""),
             remat=env.get("BENCH_REMAT", ""),
             sync=int(env.get("BENCH_SYNC", 1)))
    if s["quant"] not in ("", "int8"):
        raise BenchConfigError(
            f"unknown BENCH_QUANT={s['quant']!r} (only '' or 'int8')")
    if min(s["bs"], s["seq"], s["steps"], s["sync"]) < 1:
        raise BenchConfigError(
            "BENCH_BS, BENCH_SEQ, BENCH_STEPS and BENCH_SYNC must be >= 1")
    from dlti_tpu.config import resolve_model

    try:
        s["model_cfg"] = resolve_model(s["model"])
    except ValueError as e:
        raise BenchConfigError(str(e)) from None
    return s


def run(s: dict) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dlti_tpu.utils.platform import device_facts, enable_compilation_cache

    enable_compilation_cache()
    facts = device_facts()
    if facts["platform"] == "cpu":
        raise RuntimeError(
            "no accelerator: JAX reports platform 'cpu', and a CPU timing "
            "is not a device metric")

    from dlti_tpu.config import LoRAConfig, OptimizerConfig
    from dlti_tpu.models import LlamaForCausalLM, count_params
    from dlti_tpu.training import (
        build_optimizer, create_train_state, make_multi_step, make_train_step,
    )
    from dlti_tpu.utils.metrics import (
        chip_peak_flops, compute_mfu, device_peak_memory,
    )

    cfg = s["model_cfg"]
    if s["remat"] == "none":
        cfg = dataclasses.replace(cfg, remat=False)
    elif s["remat"]:
        cfg = dataclasses.replace(cfg, remat_policy=s["remat"])
    model = LlamaForCausalLM(cfg, LoRAConfig())
    rng = jax.random.PRNGKey(0)
    state = create_train_state(rng, model, build_optimizer(OptimizerConfig()),
                               (s["bs"], s["seq"]))
    trainable, total = count_params(state.params)
    if s["quant"] == "int8":
        from dlti_tpu.models.quantization import quantize_params_int8

        state = state.replace(
            params=quantize_params_int8(state.params, donate=True))

    batch = {
        "input_ids": jax.random.randint(rng, (1, s["bs"], s["seq"]), 0,
                                        cfg.vocab_size),
        "loss_mask": jnp.ones((1, s["bs"], s["seq"]), jnp.int32),
    }
    base_step = make_train_step(model, accum_steps=1)
    sync = s["sync"]
    if sync > 1:
        # The Trainer's steps_per_sync path: `sync` optimizer steps per
        # compiled call.
        step = make_multi_step(base_step)
        batches = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (sync,) + x.shape), batch)

        def call(state, i):
            rngs = jax.vmap(lambda j: jax.random.fold_in(rng, i * sync + j))(
                jnp.arange(sync))
            state, ms = step(state, batches, rngs)
            return state, ms["loss"][-1]
    else:
        step = jax.jit(base_step, donate_argnums=(0,))

        def call(state, i):
            state, m = step(state, batch, jax.random.fold_in(rng, i))
            return state, m["loss"]

    # Compile + one more call, outside the timed window. Every timing ends
    # in block_until_ready on the call's whole output: dispatch returns
    # before the device finishes.
    t0 = time.perf_counter()
    state, loss = jax.block_until_ready(call(state, s["steps"]))
    compile_s = time.perf_counter() - t0
    state, loss = jax.block_until_ready(call(state, s["steps"] + 1))

    t0 = time.perf_counter()
    for i in range(s["steps"]):
        state, loss = call(state, i)
    state, loss = jax.block_until_ready((state, loss))
    step_s = (time.perf_counter() - t0) / (s["steps"] * sync)

    loss = float(loss)
    if loss != loss or loss in (float("inf"), float("-inf")):
        raise RuntimeError(f"non-finite loss {loss}")
    tok_s = s["bs"] * s["seq"] / step_s
    peak_gb, peak_src = device_peak_memory()
    return {
        "metric": METRIC, "value": round(tok_s, 1), "unit": "tok/s/chip",
        "vs_baseline": round(tok_s / V100_BASELINE_TOK_S, 3),
        **facts,
        "model": s["model"], "model_layers": cfg.num_layers,
        "micro_batch_size": s["bs"], "seq_len": s["seq"],
        "step_ms": round(step_s * 1000, 1),
        "mfu_percent": round(compute_mfu(
            tok_s, total, chip_peak_flops(), trainable_params=trainable), 2),
        "loss": round(loss, 4),
        "quantize_frozen_base": s["quant"], "remat_policy": s["remat"],
        "steps_per_sync": sync,
        "compile_and_first_call_s": round(compile_s, 1),
        "peak_memory_gb": round(peak_gb, 3), "peak_memory_source": peak_src,
    }


def main() -> int:
    try:
        out, rc = run(_settings()), 0
    except BenchConfigError as e:
        out, rc = {"error": str(e)}, 2
    except Exception as e:  # noqa: BLE001 — the contract: always one JSON line
        import traceback

        traceback.print_exc()
        out, rc = {"error": f"{type(e).__name__}: {str(e)[:400]}"}, 1
    if rc:
        out = {"metric": METRIC, "value": 0.0, "unit": "tok/s/chip",
               "vs_baseline": 0.0, **out}
    print(json.dumps(out), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
