#!/usr/bin/env python
"""Training CLI — one entry point for the reference's four trainer scripts.

The reference ships one script per strategy (``training/train_baseline.py``,
``train_deepspeed_zero{1,2,3}.py``) with drifting argparse defaults
(SURVEY.md §5.6). Here a single CLI selects the strategy with ``--preset``
and the mesh with ``--num-devices/--tensor/--sequence/--expert/--pipe``
(`--data` sets the batch-row extent under ``--pipe``); everything else is
the shared typed config tree.

Examples:

    # reference baseline equivalent (1 chip, LoRA r=16, accum 16)
    python scripts/train.py --preset baseline --dataset-path data/synth \
        --model llama2_7b --tokenizer meta-llama/Llama-2-7b-hf

    # ZeRO-3 over 8 chips with TP=2 (the `deepspeed --num_gpus=8` analog)
    python scripts/train.py --preset zero3 --num-devices 4 --tensor 2 ...

    # hermetic CPU smoke (virtual 8-device mesh)
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python scripts/train.py --preset zero1 --num-devices 8 --model llama_tiny \
        --tokenizer byte --dataset-path data/synth --max-steps 3

Reference flag mapping (``train_baseline.py:27-89``): ``--model-name`` ->
``--model`` (a preset, since weights are trained from scratch or restored
from our checkpoints), ``--per-device-batch-size`` and grad-accum/lr/lora-r
keep the reference defaults (1, 16, 2e-4, 16).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

# Source checkout wins over any installed copy; an installed dlti-tpu
# serves scripts run from outside a checkout.
_repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.isdir(os.path.join(_repo_root, "dlti_tpu")):
    sys.path.insert(0, _repo_root)
del _repo_root

from dlti_tpu.telemetry.startup import install_compile_listener
from dlti_tpu.utils.platform import enable_compilation_cache

enable_compilation_cache()
# The trainer's sampler (/debug/vars, flight dumps) then carries what
# compiling and fetching programs cost (compilations, compile_seconds, ...).
install_compile_listener()


def parse_args():
    p = argparse.ArgumentParser(description="TPU-native LLM trainer",
                                formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    p.add_argument("--preset", default="baseline",
                   help="strategy: baseline | zero1 | zero2 | zero3")
    p.add_argument("--model", default="llama2_7b",
                   help="model preset name (see dlti_tpu.config.MODEL_PRESETS)")
    p.add_argument("--dataset-path", "--dataset_path", default="./data/glaive_code_full",
                   help="HF save_to_disk dir, JSONL with a `text` field, or plain-text file")
    p.add_argument("--output-dir", "--output_dir", default="./checkpoints/run")
    p.add_argument("--tokenizer", default="meta-llama/Llama-2-7b-hf",
                   help="HF tokenizer name/path, or 'byte' for the hermetic byte tokenizer")
    # Reference training defaults (train_baseline.py:27-89).
    p.add_argument("--num-train-epochs", type=int, default=1)
    p.add_argument("--max-steps", type=int, default=0, help="0 = full epochs")
    p.add_argument("--per-device-batch-size", type=int, default=1)
    p.add_argument("--gradient-accumulation-steps", type=int, default=16)
    p.add_argument("--learning-rate", type=float, default=2e-4)
    p.add_argument("--warmup-steps", type=int, default=100)
    p.add_argument("--lora-r", type=int, default=16, help="0 disables LoRA (full fine-tune)")
    p.add_argument("--max-seq-len", type=int, default=512)
    p.add_argument("--pack", action="store_true",
                   help="pack sequences to fill seq_len (perf option; reference pads)")
    p.add_argument("--prefetch-depth", type=int, default=2,
                   help="background batch-prefetch depth: gather/pack and "
                        "the host→device transfer run off the step thread, "
                        "double-buffered this deep (bit-identical loss "
                        "trajectory; 0 = legacy inline fetch)")
    # Mesh axes (the torchrun/deepspeed --num_gpus analog).
    p.add_argument("--num-devices", type=int, default=0,
                   help="DP/FSDP extent; 0 = all visible devices / "
                        "(tensor*sequence*expert)")
    p.add_argument("--tensor", type=int, default=1, help="tensor-parallel extent")
    p.add_argument("--sequence", type=int, default=1,
                   help="sequence-parallel (ring attention) extent")
    p.add_argument("--expert", type=int, default=1,
                   help="expert-parallel extent (MoE models: experts "
                        "shard over this axis)")
    p.add_argument("--pipe", type=int, default=1,
                   help="pipeline-parallel stages (GPipe schedule; "
                        "microbatches = --gradient-accumulation-steps). "
                        "Composes with every other mesh axis: under "
                        "--pipe, --data sets the batch-row extent "
                        "(ZeRO presets shard over it)")
    p.add_argument("--data", type=int, default=None,
                   help="batch-row (DP) extent under --pipe; with a "
                        "zero3 preset this is the FSDP extent; default: "
                        "the preset's own extent. Rejected without "
                        "--pipe (use --num-devices there)")
    p.add_argument("--offload-optimizer", action="store_true",
                   help="ZeRO-3 host-offload parity (ds_config_zero3.json:19-23)")
    p.add_argument("--offload-params", action="store_true",
                   help="ZeRO-3 param host-offload parity (ds_config_zero3.json:24-27)")
    p.add_argument("--fp16", action="store_true",
                   help="fp16 + dynamic loss scaling parity mode (TPU default is "
                        "bf16, which needs no scaler — ds_config fp16 block)")
    p.add_argument("--quantize-base", default="", choices=["", "int8"],
                   help="store the frozen base params weight-only quantized "
                        "during LoRA training (QLoRA-style); halves base "
                        "HBM and buys activation-saving headroom")
    p.add_argument("--remat-policy", default=None,
                   choices=["none", "nothing_saveable", "dots_saveable",
                            "dots_with_no_batch_dims_saveable",
                            "save_attn_out"],
                   help="activation-saving policy for jax.checkpoint "
                        "('none' disables remat entirely — fits at 7B bs4 "
                        "once the base is int8; default: preset's). With "
                        "neither this nor --remat-stride stated the trainer "
                        "keeps the activations of as many blocks as the "
                        "device has room for (its 'remat:' log line)")
    p.add_argument("--remat-stride", type=int, default=0,
                   help="keep every Nth block's activations (selective "
                        "remat; 0 = preset)")
    p.add_argument("--loss-chunk", type=int, default=0,
                   help="sequence-chunked cross-entropy: compute LM head + "
                        "CE this many positions at a time so full fp32 "
                        "logits never sit in HBM (0 = off; not for "
                        "--sequence > 1 or MoE)")
    p.add_argument("--steps-per-sync", type=int, default=1,
                   help="optimizer steps per compiled program call (scanned "
                        "window; same trajectory as 1, metrics stay "
                        "per-step, eval/saves land at window boundaries; "
                        "not with --offload-* or multi-host)")
    # Checkpointing (reference: save_steps=100, keep 3 — zero1:243-245).
    p.add_argument("--save-strategy", default="steps", choices=["steps", "epoch", "no"])
    p.add_argument("--save-steps", type=int, default=100)
    p.add_argument("--save-total-limit", type=int, default=3)
    p.add_argument("--no-resume", action="store_true",
                   help="skip the verified scan-latest-and-resume pass")
    p.add_argument("--fault-inject-step", default="",
                   help="deterministic trainer chaos hook 'STEP[:MODE]' "
                        "(MODE: raise | kill | save-raise | save-kill | "
                        "nan-grad | poison-batch | param-flip[:RANK]) — "
                        "crash/SIGKILL the trainer, or inject a numeric "
                        "fault (NaN grads, a deterministically-poisoned "
                        "data window, a silent param bit-flip) to drill "
                        "the sentinel's skip/rollback/quarantine/SDC "
                        "paths; also via env DLTI_TRAIN_FAULT_INJECT")
    # Numeric-fault sentinel (dlti_tpu.training.sentinel).
    p.add_argument("--no-sentinel", action="store_true",
                   help="disable the numeric-fault sentinel (per-step "
                        "nonfinite/spike detection + automatic rollback; "
                        "the in-step nonfinite update gate stays — it is "
                        "a correctness fix, not an option)")
    p.add_argument("--sentinel-rollback-after", type=int, default=3,
                   help="consecutive anomalous steps before automatic "
                        "rollback to the last verified checkpoint (0 = "
                        "detect only, never roll back)")
    p.add_argument("--sentinel-window", type=int, default=32,
                   help="rolling-median spike window (steps)")
    p.add_argument("--sentinel-min-samples", type=int, default=8,
                   help="normal steps required before spike detection "
                        "arms (cold start)")
    p.add_argument("--sentinel-loss-spike-factor", type=float, default=2.0,
                   help="loss spike threshold: latest > factor x rolling "
                        "median")
    p.add_argument("--sentinel-quarantine-after", type=int, default=2,
                   help="rollbacks implicating a data window before it is "
                        "quarantined permanently (below that it replays)")
    p.add_argument("--sdc-check-interval", type=int, default=0,
                   help="cross-rank param-digest SDC probe cadence in "
                        "optimizer steps (0 = off; multi-process runs "
                        "only) — a mismatching rank is flagged as the "
                        "suspect host, dumps a flight record, and exits "
                        "87 for the elastic supervisor to evict")
    p.add_argument("--export-dir", default=None,
                   help="write a consolidated merged-LoRA export here after training")
    p.add_argument("--init-from-hf", default=None, metavar="DIR",
                   help="initialize base weights from an HF Llama checkpoint dir "
                        "(config.json + safetensors); overrides --model's arch")
    p.add_argument("--export-hf", default=None, metavar="DIR",
                   help="write the merged model as an HF-layout checkpoint after training")
    p.add_argument("--export-peft", default=None, metavar="DIR",
                   help="write the LoRA factors as a PEFT adapter after training")
    p.add_argument("--eval-dataset", default=None, metavar="PATH",
                   help="held-out dataset (same formats as --dataset-path); "
                        "evaluated every --eval-steps optimizer steps")
    p.add_argument("--eval-steps", type=int, default=0,
                   help="eval cadence in steps (0 = never; requires "
                        "--eval-dataset)")
    p.add_argument("--metrics-csv", default="results/training_metrics.csv")
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--logging-steps", type=int, default=10)
    p.add_argument("--profile-dir", default="",
                   help="capture a jax.profiler trace window here (XProf)")
    p.add_argument("--profile-start-step", type=int, default=10)
    p.add_argument("--profile-num-steps", type=int, default=3)
    # Unified telemetry (dlti_tpu.telemetry) — host-side, always-available
    # complement to the jax.profiler device traces above.
    p.add_argument("--trace-dir", default="",
                   help="export a host-side span trace (per-step phases: "
                        "batch fetch, host→device, dispatch, sync, eval, "
                        "save) as Chrome-trace JSON here; open in Perfetto")
    p.add_argument("--trace-capacity", type=int, default=65536,
                   help="span ring-buffer capacity (most recent events kept)")
    p.add_argument("--step-log", default="",
                   help="per-step JSONL telemetry stream (rank-0): step, "
                        "loss, grad_norm, lr, tok/s/chip, MFU, HBM peak — "
                        "a superset of the reference CSV columns")
    p.add_argument("--heartbeat-interval", type=int, default=0,
                   help="multi-host heartbeat cadence in steps (rank 0 "
                        "logs straggler lag; 0 = off)")
    # Self-monitoring (dlti_tpu.telemetry.{watchdog,flightrecorder}).
    p.add_argument("--watchdog", action="store_true",
                   help="enable the anomaly watchdog: hung-step deadline "
                        "(k x rolling-median step time), throughput "
                        "collapse, heartbeat staleness, checkpoint retry "
                        "storms — alerts via "
                        "dlti_watchdog_alerts_total{rule=} + JSONL log")
    p.add_argument("--watchdog-action", default="log",
                   choices=["log", "dump", "abort"],
                   help="alert escalation: log only, also dump a flight "
                        "record, or dump + abort the run (CI chaos)")
    p.add_argument("--watchdog-hung-step-min", type=float, default=30.0,
                   help="hung-step deadline floor in seconds (the rule "
                        "fires past max(this, factor x median step time))")
    p.add_argument("--flight-dir", default="",
                   help="enable the flight recorder: fatal exceptions, "
                        "preemption stops, chaos faults (even N:kill), "
                        "and watchdog escalations dump a flight-*/ black "
                        "box here; render with scripts/postmortem.py")
    p.add_argument("--no-goodput-ledger", action="store_true",
                   help="disable the goodput ledger (telemetry.ledger): "
                        "no per-bucket wall-clock accounting, goodput "
                        "fraction, per-phase steplog fields, or stitched "
                        "elastic ledger — every site drops to one "
                        "attribute read")
    p.add_argument("--slo-goodput-floor", type=float, default=0.0,
                   help="training SLO (telemetry.slo): goodput fraction "
                        "the run must hold; time below the floor burns "
                        "the error budget, burn-rate alerts fire the "
                        "watchdog's slo_burn rule and land in slo.json "
                        "flight dumps (0 = SLO engine off)")
    p.add_argument("--slo-goodput-target", type=float, default=0.99,
                   help="fraction of wall-clock that must sit at or "
                        "above --slo-goodput-floor")
    p.add_argument("--slo-window", type=float, default=3600.0,
                   help="SLO compliance / error-budget window seconds")
    p.add_argument("--slo-burn-tiers", default="14:60:5,6:300:30",
                   help="burn-rate alert tiers 'factor:long_s:short_s,"
                        "...' (SRE multi-window multi-burn-rate)")
    p.add_argument("--no-memory-ledger", action="store_true",
                   help="disable the HBM memory ledger "
                        "(telemetry.memledger): no per-owner attribution, "
                        "hbm_* steplog fields, or memory.json in flight "
                        "dumps")
    p.add_argument("--hbm-budget-bytes", type=int, default=0,
                   help="HBM capacity for headroom accounting (0 = "
                        "auto-detect from device memory_stats(); stays "
                        "unknown on CPU, keeping the hbm_pressure rule "
                        "and headroom fields off)")
    return p.parse_args()


def load_texts(path: str) -> list:
    """Dataset dir (HF save_to_disk), JSONL with `text`, or plain text lines."""
    if os.path.isdir(path):
        jsonl = os.path.join(path, "data.jsonl")
        if os.path.isfile(jsonl):
            path = jsonl
        else:
            from datasets import load_from_disk

            return list(load_from_disk(path)["text"])
    with open(path) as f:
        first = f.readline()
        f.seek(0)
        if first.lstrip().startswith("{"):
            return [json.loads(line)["text"] for line in f if line.strip()]
        return [line.rstrip("\n") for line in f if line.strip()]


def _apply_packed_window(cfg, max_doc_len: int):
    """Exact banded attention for packed batches (see
    ModelConfig.packed_attention_window)."""
    if max_doc_len and max_doc_len < cfg.data.max_seq_len:
        cfg = cfg.replace(model=dataclasses.replace(
            cfg.model, packed_attention_window=max_doc_len))
        print(f"packed attention window: {max_doc_len} "
              f"(corpus max doc length)")
    return cfg


def build_config(args):
    import jax

    from dlti_tpu.config import (
        CheckpointConfig, DataConfig, FlightRecorderConfig, LoRAConfig,
        OptimizerConfig, SentinelConfig, SLOConfig, TelemetryConfig,
        TrainConfig, WatchdogConfig, ZeROStage, preset,
    )

    cfg = preset(args.preset, model=args.model)
    par = cfg.parallel
    if args.pipe > 1:
        # GPipe over the 'pipe' axis, composing with every other mesh
        # axis (r05): ZeRO presets shard over the --data extent (zero3:
        # fsdp), TP/SP/EP ride GSPMD inside the stages. Every flag the
        # user passed is forwarded so
        # Trainer._validate_pipeline_config rejects genuinely illegal
        # combinations loudly instead of them being silently dropped.
        # Batch-row extent: an EXPLICIT --data always wins (even --data 1
        # for a pure pipe mesh — a mesh flag is never silently dropped);
        # default inherits the preset's own extent (zero3_8dev encodes
        # fsdp=8, zero1_4dev data=4).
        preset_rows = par.fsdp if int(par.zero_stage) == 3 else par.data
        rows = args.data if args.data is not None else max(preset_rows, 1)
        if rows < 1:
            raise SystemExit(f"--data {rows} must be >= 1")
        if int(par.zero_stage) == 3 and rows == 1:
            raise SystemExit(
                "--preset zero3 with --pipe needs a batch-row extent for "
                "the FSDP axis: pass --data N or use a zero3_Ndev preset "
                "(fsdp=1 would silently disable ZeRO-3 param sharding)")
        data_ext, fsdp_ext = rows, 1
        if int(par.zero_stage) == 3:
            data_ext, fsdp_ext = 1, rows
        mesh_n = (args.pipe * args.tensor * args.sequence * args.expert
                  * rows)
        if args.num_devices and args.num_devices != mesh_n:
            raise SystemExit(
                f"--num-devices {args.num_devices} conflicts with --pipe "
                f"{args.pipe} (the pipe mesh uses exactly "
                f"pipe*tensor*sequence*expert*data = {mesh_n} devices; "
                f"drop --num-devices or fix --data)")
        par = par.__class__(zero_stage=par.zero_stage,
                            pipe=args.pipe, tensor=args.tensor,
                            sequence=args.sequence, expert=args.expert,
                            data=data_ext, fsdp=fsdp_ext,
                            offload_optimizer=args.offload_optimizer,
                            offload_params=args.offload_params)
    else:
        if args.data is not None:
            # Loud-reject rule: a mesh flag must never be silently
            # dropped. Without --pipe the DP/FSDP extent is
            # --num-devices.
            raise SystemExit(
                f"--data {args.data} only applies under --pipe; without "
                f"it use --num-devices to set the DP/FSDP extent")
        n = args.num_devices or max(
            jax.device_count() // (args.tensor * args.sequence
                                   * args.expert), 1
        )
        if int(par.zero_stage) == 3:
            par = par.__class__(zero_stage=par.zero_stage, fsdp=n,
                                tensor=args.tensor, sequence=args.sequence,
                                expert=args.expert,
                                offload_optimizer=args.offload_optimizer,
                                offload_params=args.offload_params)
        else:
            par = par.__class__(zero_stage=par.zero_stage, data=n,
                                tensor=args.tensor, sequence=args.sequence,
                                expert=args.expert,
                                offload_optimizer=args.offload_optimizer,
                                offload_params=args.offload_params)

    dp = par.data * par.fsdp
    from dlti_tpu.utils.experiment import create_experiment_name

    model_cfg = cfg.model
    if args.fp16:
        # fp16 parity mode: compute and store in fp16 (the scaler handles
        # overflow); without --fp16 the TPU default bf16 stays.
        model_cfg = dataclasses.replace(model_cfg, dtype="float16",
                                        param_dtype="float16")
    if args.remat_policy == "none":
        model_cfg = dataclasses.replace(model_cfg, remat=False)
    elif args.remat_policy:
        model_cfg = dataclasses.replace(model_cfg,
                                        remat_policy=args.remat_policy)
    if args.remat_stride:
        model_cfg = dataclasses.replace(model_cfg,
                                        remat_stride=args.remat_stride)
    if args.remat_policy or args.remat_stride:
        # A stated remat is held to: the trainer's own count of blocks
        # that keep their activations (training.remat_plan) stands aside.
        model_cfg = dataclasses.replace(model_cfg, remat_keep_blocks=0)

    return cfg.replace(
        model=model_cfg,
        parallel=par,
        lora=LoRAConfig(enabled=args.lora_r > 0, r=max(args.lora_r, 1),
                        alpha=2 * max(args.lora_r, 1)),
        optimizer=OptimizerConfig(learning_rate=args.learning_rate,
                                  warmup_steps=args.warmup_steps),
        data=DataConfig(dataset_path=args.dataset_path, tokenizer=args.tokenizer,
                        max_seq_len=args.max_seq_len, pack_sequences=args.pack,
                        prefetch_depth=args.prefetch_depth),
        checkpoint=CheckpointConfig(output_dir=args.output_dir,
                                    save_strategy=args.save_strategy,
                                    save_steps=args.save_steps,
                                    save_total_limit=args.save_total_limit,
                                    resume=not args.no_resume),
        train=TrainConfig(num_epochs=args.num_train_epochs,
                          max_steps=args.max_steps,
                          micro_batch_size=args.per_device_batch_size * dp,
                          grad_accum_steps=args.gradient_accumulation_steps,
                          logging_steps=args.logging_steps, seed=args.seed,
                          metrics_csv=args.metrics_csv, fp16=args.fp16,
                          quantize_frozen_base=args.quantize_base,
                          loss_chunk=args.loss_chunk,
                          steps_per_sync=args.steps_per_sync,
                          fault_inject_step=args.fault_inject_step,
                          eval_steps=args.eval_steps,
                          profile_dir=args.profile_dir,
                          profile_start_step=args.profile_start_step,
                          profile_num_steps=args.profile_num_steps,
                          sentinel=SentinelConfig(
                              enabled=not args.no_sentinel,
                              rollback_after=args.sentinel_rollback_after,
                              window=args.sentinel_window,
                              min_samples=args.sentinel_min_samples,
                              loss_spike_factor=args.sentinel_loss_spike_factor,
                              quarantine_after=args.sentinel_quarantine_after,
                              sdc_check_interval=args.sdc_check_interval)),
        telemetry=TelemetryConfig(
            trace_dir=args.trace_dir,
            trace_capacity=args.trace_capacity,
            step_log_path=args.step_log,
            heartbeat_interval_steps=args.heartbeat_interval,
            goodput_ledger=not args.no_goodput_ledger,
            memory_ledger=not args.no_memory_ledger,
            hbm_budget_bytes=args.hbm_budget_bytes,
            slo=SLOConfig(
                enabled=args.slo_goodput_floor > 0,
                window_s=args.slo_window,
                burn_tiers=args.slo_burn_tiers,
                goodput_floor=args.slo_goodput_floor,
                goodput_target=args.slo_goodput_target),
            watchdog=WatchdogConfig(
                enabled=args.watchdog,
                action=args.watchdog_action,
                hung_step_min_s=args.watchdog_hung_step_min,
                heartbeat_stale_s=(600.0 if args.heartbeat_interval else 0.0),
                alert_log_path=(os.path.join(args.flight_dir,
                                             "watchdog_alerts.jsonl")
                                if args.flight_dir else "")),
            flight_recorder=FlightRecorderConfig(dir=args.flight_dir)),
        experiment_name=create_experiment_name(
            par.num_devices, int(par.zero_stage)),
    )


def main() -> None:
    args = parse_args()

    # Multi-host rendezvous when spawned by scripts/launch.py (the
    # LOCAL_RANK/WORLD_SIZE contract analog); no-op single-process.
    from dlti_tpu.launcher import maybe_initialize_from_env

    maybe_initialize_from_env()

    cfg = build_config(args)

    # Elastic launch (scripts/launch.py --elastic): when this generation
    # runs at less than the full slot count, shrink the mesh batch axes
    # to the surviving devices and recompute grad-accum so the GLOBAL
    # batch schedule (rows per optimizer step, steps/epoch, rng folds) is
    # exactly the full-size run's — a resumed shrunk generation replays
    # the same batches the dead world would have.
    from dlti_tpu.training.elastic import maybe_reshape_from_env

    cfg = maybe_reshape_from_env(cfg)

    base_params = None
    if args.init_from_hf:
        from dlti_tpu.models import load_hf_checkpoint

        # config.json supplies the architecture; the preset keeps the
        # performance fields (bf16 dtypes, remat, attention impl, seq len) —
        # an fp32 checkpoint must not silently flip training to fp32.
        perf_fields = dict(
            dtype=cfg.model.dtype, param_dtype=cfg.model.param_dtype,
            remat=cfg.model.remat, remat_policy=cfg.model.remat_policy,
            attention_impl=cfg.model.attention_impl,
            flash_block_q=cfg.model.flash_block_q,
            flash_block_kv=cfg.model.flash_block_kv,
        )
        base_params, hf_model_cfg = load_hf_checkpoint(
            args.init_from_hf, **perf_fields)
        if hf_model_cfg != cfg.model:
            print(f"model arch from {args.init_from_hf}/config.json "
                  f"(overrides --model={args.model})")
            cfg = cfg.replace(model=hf_model_cfg)

    from dlti_tpu.data import get_tokenizer, make_batches
    from dlti_tpu.training import Trainer

    print(f"experiment: {cfg.experiment_name}")
    print(f"mesh: data={cfg.parallel.data} fsdp={cfg.parallel.fsdp} "
          f"tensor={cfg.parallel.tensor} sequence={cfg.parallel.sequence} "
          f"pipe={cfg.parallel.pipe}")

    if os.path.isfile(os.path.join(args.dataset_path, "meta.json")):
        # Memory-mapped token store (scripts/prepare_dataset.py
        # --write-token-store): corpus-scale input, O(rows) host RAM.
        from dlti_tpu.data.streaming import StreamingTokenDataset

        # Fail fast on config mismatches: the rows are baked at prepare
        # time, so the run config must match them (a tokenizer mismatch
        # raises inside the dataset; a different seq_len silently changes
        # the workload).
        try:
            dataset = StreamingTokenDataset(
                args.dataset_path,
                micro_batch_size=cfg.train.micro_batch_size,
                grad_accum_steps=cfg.train.grad_accum_steps,
                shuffle_seed=cfg.data.shuffle_seed,
                expect_tokenizer=cfg.data.tokenizer,
            )
        except ValueError as e:
            raise SystemExit(str(e))
        if dataset.seq_len != cfg.data.max_seq_len:
            raise SystemExit(
                f"token store {args.dataset_path} was written with "
                f"seq_len={dataset.seq_len}, but --max-seq-len is "
                f"{cfg.data.max_seq_len}; re-prepare or pass the matching "
                f"--max-seq-len")
        print(f"dataset: memory-mapped token store {args.dataset_path} "
              f"({dataset._ids.shape[0]} rows x {dataset.seq_len}, "
              f"packed={dataset.packed})")
        if dataset.packed and cfg.parallel.pipe > 1:
            raise SystemExit(
                "this token store is packed, and packed batches are not "
                "supported under --pipe (the pipelined stage body takes "
                "no segment mask); re-prepare without --pack")
        if dataset.packed:
            cfg = _apply_packed_window(cfg, dataset.max_doc_len)
    else:
        texts = load_texts(args.dataset_path)
        print(f"dataset: {len(texts)} examples from {args.dataset_path}")
        tok = get_tokenizer(cfg.data.tokenizer)
        dataset = make_batches(
            texts, tok,
            seq_len=cfg.data.max_seq_len,
            micro_batch_size=cfg.train.micro_batch_size,
            grad_accum_steps=cfg.train.grad_accum_steps,
            shuffle_seed=cfg.data.shuffle_seed,
            pack=cfg.data.pack_sequences,
        )
        if cfg.data.pack_sequences and dataset.sequences:
            cfg = _apply_packed_window(cfg, max(
                min(len(s), cfg.data.max_seq_len) for s in dataset.sequences))
    print(f"steps/epoch: {dataset.steps_per_epoch()}")

    eval_dataset = None
    if args.eval_dataset:
        if not cfg.train.eval_steps:
            raise SystemExit("--eval-dataset needs --eval-steps > 0")
        if os.path.isfile(os.path.join(args.eval_dataset, "meta.json")):
            # Same formats as --dataset-path: a token store evals directly.
            from dlti_tpu.data import StreamingTokenDataset

            try:
                eval_dataset = StreamingTokenDataset(
                    args.eval_dataset,
                    micro_batch_size=cfg.train.micro_batch_size,
                    grad_accum_steps=1,
                    shuffle_seed=None,  # fixed order: eval loss is comparable
                    expect_tokenizer=cfg.data.tokenizer,
                )
            except ValueError as e:
                raise SystemExit(str(e))
            if eval_dataset.seq_len != cfg.data.max_seq_len:
                raise SystemExit(
                    f"eval token store {args.eval_dataset} was written with "
                    f"seq_len={eval_dataset.seq_len}, but --max-seq-len is "
                    f"{cfg.data.max_seq_len}")
            print(f"eval dataset: token store {args.eval_dataset} "
                  f"({eval_dataset._ids.shape[0]} rows)")
            if eval_dataset.packed and cfg.parallel.pipe > 1:
                raise SystemExit(
                    "the eval token store is packed, and packed batches "
                    "are not supported under --pipe; re-prepare the eval "
                    "split without --pack")
            if (eval_dataset.packed and cfg.model.packed_attention_window
                    and eval_dataset.max_doc_len
                    > cfg.model.packed_attention_window):
                # The banded window is exact only if it covers the longest
                # document either split contains; widen it to stay exact
                # for eval (>= seq_len disables the band entirely).
                widened = (0 if eval_dataset.max_doc_len
                           >= cfg.data.max_seq_len
                           else eval_dataset.max_doc_len)
                cfg = cfg.replace(model=dataclasses.replace(
                    cfg.model, packed_attention_window=widened))
                print(f"packed attention window widened to {widened or 'off'}"
                      f" (eval corpus max doc length)")
        else:
            eval_texts = load_texts(args.eval_dataset)
            print(f"eval dataset: {len(eval_texts)} examples from "
                  f"{args.eval_dataset}")
            eval_dataset = make_batches(
                eval_texts, get_tokenizer(cfg.data.tokenizer),
                seq_len=cfg.data.max_seq_len,
                micro_batch_size=cfg.train.micro_batch_size,
                grad_accum_steps=1,
                shuffle_seed=None,  # fixed order: eval loss is comparable
            )
        if eval_dataset.steps_per_epoch() == 0:
            raise SystemExit(
                f"eval dataset yields zero batches: it has fewer rows than "
                f"one global batch ({cfg.train.micro_batch_size}); shrink "
                f"--per-device-batch-size or grow the eval split")

    trainer = Trainer(cfg, base_params=base_params)
    state, record = trainer.train(dataset=dataset, eval_dataset=eval_dataset)

    if args.export_dir:
        from dlti_tpu.checkpoint import export_merged_model

        export_merged_model(args.export_dir, state.params, cfg)
        print(f"merged export -> {args.export_dir}")
    if args.export_peft:
        import jax

        from dlti_tpu.models import save_peft_adapter

        save_peft_adapter(args.export_peft, jax.device_get(state.params), cfg.lora)
        print(f"PEFT adapter -> {args.export_peft}")
    if args.export_hf:
        import jax

        from dlti_tpu.models import merge_lora_params, save_hf_checkpoint

        merged = merge_lora_params(jax.device_get(state.params), alpha=cfg.lora.alpha)
        save_hf_checkpoint(args.export_hf, merged, cfg.model)
        print(f"HF checkpoint -> {args.export_hf}")


if __name__ == "__main__":
    main()
