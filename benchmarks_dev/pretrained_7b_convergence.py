"""Pretrained-7B convergence.

The reference's recorded 7B trajectory fine-tunes *pretrained*
Llama-2-7B and goes 0.94 -> ~0.60-0.78 on glaive
(``/root/reference/training/train.ipynb:334`` ff., cell 18). Literal
Llama-2 weights are unreachable in this offline image (zero egress), so
this run reproduces the *semantics* at full 7B scale with the repo's own
trained artifact, exactly like ``results/hf_interop_pretrained_300m.json``
did at 300M:

  1. load the consolidated 7B glaive export (scripts/train.py
     --export-dir) host-side (``load_exported_model`` — no device needed to read it)
  2. fine-tune from it on 400 *held-out* glaive pairs (variants
     20000-20399; training saw 0-19999) through the production
     ``Trainer(base_params=...)`` path with LoRA r=16 + int8 frozen base
     — the same config as the training headline
  3. a short random-init contrast run makes the pretrained-start gap
     explicit (corpus-level first-step loss vs ~11 cold)

Writes ``results/convergence_7b_pretrained_tpu.json`` with the full
per-step loss curve (all steps reported, no cherry-picking).

Smoke test (no chip, 300M export):
    python benchmarks_dev/pretrained_7b_convergence.py --cpu
"""

import argparse
import dataclasses
import json
import logging
import os
import re
import sys
import tempfile
import time

_repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _repo)
os.chdir(_repo)


class _Capture(logging.Handler):
    """Per-step losses only reach the logger ('step N | loss X | ...')."""

    def __init__(self):
        super().__init__()
        self.losses = []

    def emit(self, record):
        m = re.match(r"step (\d+) \| loss ([0-9.]+)", record.getMessage())
        if m:
            self.losses.append(round(float(m.group(2)), 4))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--export", default="exports/glaive_7b_r05")
    ap.add_argument("--cpu", action="store_true",
                    help="smoke test: 300M export, no int8, tiny step count")
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--contrast-steps", type=int, default=3)
    ap.add_argument("--bs", type=int, default=0, help="0 = auto (4 chip / 2 cpu)")
    ap.add_argument("--json-out", default="")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        args.export = (args.export if os.path.isdir(args.export)
                       and "7b" not in args.export else "exports/glaive_300m")
        args.steps = min(args.steps, 8)
    bs = args.bs or (2 if args.cpu else 4)

    from dlti_tpu.checkpoint.export import load_exported_model
    from dlti_tpu.config import (
        CheckpointConfig, Config, DataConfig, LoRAConfig, OptimizerConfig,
        ParallelConfig, TrainConfig,
    )
    from dlti_tpu.data import ByteTokenizer, make_batches
    from dlti_tpu.training.trainer import Trainer
    from datasets import load_from_disk

    t0 = time.time()
    params, full_cfg = load_exported_model(args.export)
    mc = full_cfg.model
    print(f"export {args.export} loaded in {time.time()-t0:.0f}s", flush=True)

    texts = list(load_from_disk("data/glaive_eval")["text"])
    print(f"{len(texts)} held-out texts (variants 20000+)", flush=True)

    # Same winning config as the training headline (int8 frozen base, no
    # remat) so the convergence run and the throughput claim share a
    # config; CPU smoke keeps bf16->fp32 and remat off for speed.
    mc_ft = dataclasses.replace(mc, remat=False, max_seq_len=512)
    tmp = tempfile.mkdtemp(prefix="conv7b_")

    def run(tag, base_params, max_steps):
        cfg = Config(
            model=mc_ft,
            lora=LoRAConfig(enabled=True, r=16, alpha=32, dropout=0.0),
            optimizer=OptimizerConfig(learning_rate=2e-4, warmup_steps=4),
            parallel=ParallelConfig(),
            data=DataConfig(max_seq_len=512, tokenizer="byte"),
            checkpoint=CheckpointConfig(output_dir=os.path.join(tmp, tag),
                                        save_strategy="no"),
            train=TrainConfig(micro_batch_size=bs, grad_accum_steps=1,
                              max_steps=max_steps, logging_steps=1,
                              num_epochs=10,
                              quantize_frozen_base="" if args.cpu else "int8",
                              metrics_csv=os.path.join(tmp, f"{tag}.csv")),
            experiment_name=tag,
        )
        ds = make_batches(texts, ByteTokenizer(), seq_len=512,
                          micro_batch_size=bs, grad_accum_steps=1,
                          shard_by_host=False)
        tr = Trainer(cfg, base_params=base_params)
        cap = _Capture()
        tr.logger.addHandler(cap)
        t = time.time()
        try:
            state, record = tr.train(dataset=ds)
        finally:
            tr.logger.removeHandler(cap)
        dt = time.time() - t
        print(f"{tag}: {len(cap.losses)} steps in {dt:.0f}s "
              f"first={cap.losses[0] if cap.losses else None} "
              f"final={record.final_loss:.4f}", flush=True)
        return cap.losses, round(float(record.final_loss), 4), round(dt, 1)

    ft_losses, ft_final, ft_s = run("from_pretrained", params, args.steps)
    ri_losses, ri_final, ri_s = run("random_init", None, args.contrast_steps)

    scale = ("CPU SMOKE of the runner on the 300M export (NOT 7B-scale "
             "evidence — proves the script end-to-end)" if args.cpu
             else "consolidated trained 7B glaive export")
    art = {
        "what": f"pretrained convergence semantics: {scale} -> "
                "Trainer(base_params=...) LoRA r=16 "
                f"{'' if args.cpu else 'int8-base '}fine-tune on 400 "
                "HELD-OUT glaive pairs; random-init contrast shows the "
                "pretrained base starts at corpus loss, not cold. "
                "Reference trajectory: pretrained Llama-2-7B 0.94 -> "
                "~0.60-0.78 (train.ipynb:334 ff.). Literal Llama-2 "
                "weights are unreachable offline (zero egress), so the "
                "repo's own trained export stands in as the pretrained "
                "base — same mechanism.",
        "export": args.export,
        "steps": len(ft_losses),
        "micro_batch_size": bs,
        "finetune_losses_from_pretrained": ft_losses,
        "finetune_final_loss_from_pretrained": ft_final,
        "finetune_seconds": ft_s,
        "finetune_losses_random_init_contrast": ri_losses,
        "finetune_final_loss_random_init_contrast": ri_final,
        "reference_parity": "train.ipynb:334 ff. (pretrained 7B base, "
                            "loss starts ~0.94 not ~11)",
        "platform": "cpu-smoke" if args.cpu else jax.devices()[0].device_kind,
    }
    out = args.json_out or ("results/convergence_7b_pretrained_cpu_smoke.json"
                            if args.cpu
                            else "results/convergence_7b_pretrained_tpu.json")
    with open(out, "w") as f:
        json.dump(art, f, indent=1)
    print("ARTIFACT_WRITTEN", out, flush=True)
    assert ft_losses[0] < 2.5, f"pretrained start too high: {ft_losses[0]}"
    assert ri_losses[0] > 5.0, f"random-init start too low: {ri_losses[0]}"
    print("CONVERGENCE_OK", flush=True)


if __name__ == "__main__":
    main()
