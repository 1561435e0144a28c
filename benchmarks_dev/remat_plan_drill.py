"""What a kept block costs the train step, and what the chip has room for.

The trainer's own step at the shape of ``train.mistral_7b.lora_sft`` (the
configuration's file, the cell's rows, sequence length and LoRA rank; one
seeded state for the whole run), compiled once for each count of kept
blocks given, in ascending order in ONE process (``peak_bytes_in_use``
never falls, so each count's reading is its own only that way). A line a
count in ``--out``: the compiler's ``memory_analysis()`` (argument, output,
alias, temp bytes), the closed form of ``dlti_tpu/training/remat_plan.py``
beside it, ``memory_stats()`` after the steps, the host's seconds a step,
and from three steps under the profiler the program's device time, how
often ``dlti_flash_attention_fwd`` ran a step and the flash kernels'
time. A count the compiler refuses for memory gets a line with its first
words and the run goes on to ``--long``: ``K:N`` runs N more steps at K
kept blocks and reads the peak every N/8 of them, to show that it does not
creep.

    chiprun --timeout 3000 -- python3 benchmarks_dev/remat_plan_drill.py \\
        --keep 0,1,2,4,6,7 --long 6:200 --out chiprun_out/remat_plan.jsonl

``--tiny`` runs llama_tiny at 2 x 128 on the CPU in a minute (no device
figures: the CPU reports neither memory nor a device trace). ``--keep -1``
is the rule's own choice. ``--described`` runs nothing: it compiles each
count here, without the chip, for a v5e that is described and not attached
(shapes for the state and the batch, ~90 s a count), and writes the
compiler's bytes alone: they are the chip's to the byte, and past ~14.3 GiB
the compiler fits a program by its own rematerialisation (its code shrinks
tenfold) where one expects a refusal. ``results/remat_plan_v5e.jsonl`` is this file's
output on one v5e chip (PERF.md section 6, PR 54).
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark", "lib")]
T0 = time.time()


def say(*a):
    print("[%6.1f]" % (time.time() - T0), *a, flush=True)


V5E_BYTES_LIMIT = 16_909_336_064  # memory_stats()["bytes_limit"], one v5e


def cell_config(tiny: bool, described: bool = False):
    from dlti_tpu.config import (
        MODEL_PRESETS, Config, DataConfig, LoRAConfig, ModelConfig,
        OptimizerConfig, TelemetryConfig, TrainConfig,
    )

    with open(os.path.join(
            ROOT, "benchmark/cells/train.mistral_7b.lora_sft.json")) as fh:
        cell = json.load(fh)["args"]
    budget = 0
    if tiny:
        # (the CPU states no limit: one that leaves room for one block)
        model = dataclasses.replace(MODEL_PRESETS["llama_tiny"], remat=True)
        rows, seq, budget = 2, 128, 3 << 20
    else:
        from chip_child import model_fields

        with open(os.path.join(
                ROOT, "benchmark/configs/mistral_7b.json")) as fh:
            model = ModelConfig(**model_fields(json.load(fh)))
        rows = int(cell["--per-device-batch-size"])
        seq = int(cell["--max-seq-len"])
    if described:
        # (the CPU resolves "auto" to XLA's attention; main() makes
        # "flash" mean the kernel as the chip lowers it)
        model = dataclasses.replace(model, attention_impl="flash")
        budget = V5E_BYTES_LIMIT
    r = int(cell["--lora-r"])
    return Config(
        model=model, lora=LoRAConfig(enabled=True, r=r, alpha=2 * r),
        data=DataConfig(max_seq_len=seq, pack_sequences=True),
        optimizer=OptimizerConfig(warmup_steps=int(cell["--warmup-steps"])),
        train=TrainConfig(micro_batch_size=rows, grad_accum_steps=1),
        telemetry=TelemetryConfig(hbm_budget_bytes=budget))


def packed_batch(cfg, seed: int) -> dict:
    """One step's rows: seeded tokens in documents of ~400, packed."""
    import numpy as np

    from dlti_tpu.data.pipeline import packed_loss_mask, packed_positions

    rng = np.random.default_rng(seed)
    rows, seq = cfg.train.micro_batch_size, cfg.data.max_seq_len
    ids = rng.integers(3, cfg.model.vocab_size, (rows, seq), dtype=np.int32)
    seg = np.zeros((rows, seq), np.int32)
    for row in seg:
        at, doc = 0, 1
        while at < seq:
            n = int(rng.integers(seq // 8, seq // 3))
            row[at:at + n] = doc
            at, doc = at + n, doc + 1
    batch = {"input_ids": ids, "loss_mask": packed_loss_mask(seg),
             "segment_ids": seg, "positions": packed_positions(seg)}
    return {k: v[None] for k, v in batch.items()}


def memory_stats() -> dict:
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    return {k: int(v) for k, v in stats.items() if isinstance(v, int)}


def traced(run_step, steps: int) -> dict:
    """``steps`` steps under the profiler: the step program's median
    device ms, flash forward executions and the flash kernels' ms a step.
    Empty where the trace holds no device plane (the CPU)."""
    import jax
    from jax.profiler import ProfileData

    work = tempfile.mkdtemp(prefix="remat_drill_")
    try:
        jax.profiler.start_trace(work)
        for _ in range(steps):
            run_step()
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(work, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            return {}
        programs, fwd, flash_ns = [], 0, 0
        for plane in ProfileData.from_file(found[-1]).planes:
            if not plane.name.startswith("/device:TPU:0"):
                continue
            for line in plane.lines:
                if line.name == "XLA Modules":
                    programs += [ev.duration_ns for ev in line.events
                                 if "train_step" in ev.name]
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        if "flash_attention" in ev.name:
                            flash_ns += ev.duration_ns
                            fwd += "flash_attention_fwd" in ev.name
        if not programs:
            return {}
        return {"step_device_ms": statistics.median(programs) / 1e6,
                "flash_fwd_runs_a_step": fwd / len(programs),
                "flash_ms_a_step": flash_ns / 1e6 / len(programs)}
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--keep", default="0,1,2,4")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--long", default="", help="K:N")
    ap.add_argument("--out", default="chiprun_out/remat_plan.jsonl")
    ap.add_argument("--seed", type=int, default=54)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--described", action="store_true")
    args = ap.parse_args()
    if args.described:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    from dlti_tpu.models import build_model
    from dlti_tpu.telemetry.memledger import executable_memory_analysis
    from dlti_tpu.training.trainer import Trainer

    cfg = cell_config(args.tiny, args.described)
    device = jax.devices()[0]
    say("device", device.platform, device.device_kind)
    trainer = Trainer(cfg)
    batch = packed_batch(cfg, args.seed)
    key = jax.random.PRNGKey(args.seed)
    if args.described:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        from dlti_tpu.ops import attention
        from dlti_tpu.training.state import create_train_state

        jax.config.update("jax_enable_compilation_cache", False)
        attention._kernel_path = lambda: "pallas"
        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        state, batch, key = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            (jax.eval_shape(lambda k: create_train_state(
                k, trainer.model, trainer.tx,
                (cfg.train.micro_batch_size, cfg.data.max_seq_len)), key),
             batch, key))
    else:
        state = trainer.init_state()
        jax.block_until_ready(state)
        batch = jax.device_put(batch)
    planned = trainer.plan_remat(state)
    limit = planned.limit_bytes
    say(planned.line())
    say("after init", json.dumps(memory_stats()))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    out = open(args.out, "a", buffering=1)

    def emit(row: dict) -> None:
        row = {"device": device.device_kind, "layers": cfg.model.num_layers,
               "rows": cfg.train.micro_batch_size,
               "seq_len": cfg.data.max_seq_len, **row}
        out.write(json.dumps(row) + "\n")
        say(json.dumps(row))

    def compiled_at(keep: int):
        trainer.model = build_model(
            dataclasses.replace(cfg.model, remat_keep_blocks=keep),
            cfg.lora, trainer.mesh)
        t = time.time()
        program = trainer._build_step(state).lower(
            state, batch, key).compile()
        return program, time.time() - t

    def timed(program, steps: int) -> list:
        nonlocal state
        seconds = []
        for _ in range(steps):
            t = time.time()
            state, metrics = program(state, batch, key)
            jax.block_until_ready(metrics)
            seconds.append(time.time() - t)
        return seconds

    def step_once(program):
        nonlocal state
        state, metrics = program(state, batch, key)
        jax.block_until_ready(metrics)

    programs = {}
    for keep in sorted({planned.keep_blocks if int(k) < 0 else int(k)
                        for k in args.keep.split(",")}):
        closed = dataclasses.replace(planned, keep_blocks=keep, why_not="")
        row = {"keep_blocks": keep, "bytes_limit": limit,
               "closed_form_block_bytes": closed.block_bytes,
               "closed_form_base_bytes": closed.base_bytes,
               "closed_form_planned_bytes": closed.planned_bytes}
        try:
            program, row["compile_s"] = compiled_at(keep)
        except Exception as e:  # the compiler's refusal is the reading
            emit({**row, "refused": str(e).strip().splitlines()[0][:300]})
            continue
        programs[keep] = program
        row.update(executable_memory_analysis(program))
        if args.described:
            emit({**row, "described": True})
            continue
        timed(program, 2)
        seconds = timed(program, args.steps)
        row["step_host_ms"] = 1e3 * statistics.median(seconds)
        row.update(traced(lambda: step_once(program), 3))
        row["memory_stats"] = memory_stats()
        emit(row)

    if args.long and not args.described:
        keep, steps = (int(v) for v in args.long.split(":"))
        program = programs.get(keep) or compiled_at(keep)[0]
        peaks, medians = [], []
        for _ in range(8):
            medians.append(1e3 * statistics.median(
                timed(program, max(1, steps // 8))))
            peaks.append(memory_stats().get("peak_bytes_in_use", 0))
        emit({"long_run_keep_blocks": keep, "steps": 8 * max(1, steps // 8),
              "peak_bytes_in_use_every_eighth": peaks,
              "step_host_ms_every_eighth": medians,
              "memory_stats": memory_stats()})


if __name__ == "__main__":
    main()
