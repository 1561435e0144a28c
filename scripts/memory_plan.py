#!/usr/bin/env python
"""Static HBM capacity planner — "will it fit?" answered BEFORE compiling.

The paper-plan half of the memory ledger
(``dlti_tpu/telemetry/memledger.py``): the ledger measures where device
memory actually went at runtime; this script predicts the same owner
buckets from the model/engine configs alone, so a 7B serving deployment
(or a fine-tune) can be sized on paper — and the two are cross-checked
against each other in ``tests/test_memledger.py`` on a tiny CPU model.

Training plan (per chip, no sharding):
    params      = num_params x sizeof(param_dtype)
    optimizer   = 2 x trainable x 4        (AdamW m+v, always fp32)
    grad_buffers = trainable x 4           (transient; peak-relevant)
Serving plan:
    params      = num_params x sizeof(param_dtype)
    kv_pool     = 2 x layers x kv_heads x head_dim x sizeof(kv_dtype)
                  x block_size x num_blocks
    kv/token    = the same without the pool factors -> max resident
                  tokens, and max concurrent seqs at max_model_len

Usage:
    python scripts/memory_plan.py --model llama2_7b --budget-gb 16
    python scripts/memory_plan.py --model llama2_7b --serving \\
        --num-blocks 2048 --kv-dtype int8 --budget-gb 16
    python scripts/memory_plan.py ... --json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional

# Source checkout wins over any installed copy.
_repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.isdir(os.path.join(_repo_root, "dlti_tpu")):
    sys.path.insert(0, _repo_root)
del _repo_root

from dlti_tpu.config import (  # noqa: E402
    MODEL_PRESETS, Config, DataConfig, LoRAConfig, ModelConfig, TrainConfig,
)

# Storage bytes per element (matches dlti_tpu.utils.dtypes resolution).
DTYPE_BYTES = {
    "float32": 4, "fp32": 4, "f32": 4,
    "bfloat16": 2, "bf16": 2, "float16": 2, "fp16": 2,
    "int8": 1, "fp8": 1,
}


def _dtype_bytes(name: str) -> int:
    try:
        return DTYPE_BYTES[name.lower()]
    except KeyError:
        raise ValueError(f"unknown dtype {name!r}; one of "
                         f"{sorted(DTYPE_BYTES)}") from None


def lora_trainable_params(cfg: ModelConfig, r: int = 16,
                          target_modules: tuple = ("q_proj", "k_proj",
                                                   "v_proj", "o_proj"),
                          ) -> int:
    """Adapter parameter count for the reference LoRA graft: per layer and
    per targeted projection, two factors of shape (in, r) and (r, out). A
    family that states its own targets (``ModelConfig.lora_targets``: the
    jamba family's attention and state-space projections) is counted by
    those, each layer by the projections its mixer has."""
    h = cfg.hidden_size
    hd = cfg.resolved_head_dim
    dims = {
        "q_proj": (h, cfg.num_heads * hd),
        "k_proj": (h, cfg.num_kv_heads * hd),
        "v_proj": (h, cfg.num_kv_heads * hd),
        "o_proj": (cfg.num_heads * hd, h),
    }
    targets = cfg.lora_targets or target_modules
    if cfg.is_jamba:
        d_in = cfg.mamba_inner_size
        by_kind = {"A": dims, "S": {
            "in_proj": (h, 2 * d_in),
            "x_proj": (d_in, cfg.mamba_dt_rank + 2 * cfg.mamba_state_size),
            "dt_proj": (cfg.mamba_dt_rank, d_in),
            "out_proj": (d_in, h)}}
        return sum(r * (i + o) for kind in cfg.layer_pattern
                   for m, (i, o) in by_kind[kind].items() if m in targets)
    per_layer = sum(r * (i + o) for m, (i, o) in dims.items()
                    if m in targets)
    return cfg.num_layers * per_layer


def _proj_dims(cfg: ModelConfig) -> dict:
    """(in, out) of every LoRA-targetable projection — the same dims the
    engine's AdapterPool walks off the live param tree."""
    h = cfg.hidden_size
    hd = cfg.resolved_head_dim
    inter = cfg.intermediate_size
    return {
        "q_proj": (h, cfg.num_heads * hd),
        "k_proj": (h, cfg.num_kv_heads * hd),
        "v_proj": (h, cfg.num_kv_heads * hd),
        "o_proj": (cfg.num_heads * hd, h),
        "gate_proj": (h, inter),
        "up_proj": (h, inter),
        "down_proj": (inter, h),
    }


def adapter_pool_bytes(cfg: ModelConfig, num_slots: int, rank: int = 16,
                       targets: tuple = ("q_proj", "k_proj",
                                         "v_proj", "o_proj")) -> int:
    """HBM the stacked multi-LoRA adapter pool pins: per layer and target,
    f32 A (P, in, r) + B (P, r, out) + scale (P,) with P = num_slots + 1
    (row 0 is the all-zero base row). Must equal
    ``dlti_tpu.serving.adapters.plan_pool_bytes`` — cross-checked against
    it AND the measured ``lora_adapters`` ledger owner in tier-1."""
    if num_slots <= 0:
        return 0
    dims = _proj_dims(cfg)
    unknown = [t for t in targets if t not in dims]
    if unknown:
        raise ValueError(f"unknown adapter targets {unknown}; "
                         f"one of {sorted(dims)}")
    per_row = sum(dims[t][0] * rank + rank * dims[t][1] + 1
                  for t in targets)
    return (num_slots + 1) * cfg.num_layers * per_row * 4


def _pool_layers(cfg: ModelConfig, group: int) -> int:
    """Layers that keep a block pool in ``group`` of ``kv_group_windows``
    (0: the group whose pools have ``--num-blocks``): attention layers with
    keys of their own. A looped stack keeps an entry a pass."""
    kinds = cfg.layer_pattern or "*" * cfg.num_layers
    return cfg.ut_steps * sum(
        k in "*DA" and cfg.kv_group_of_layer(i) == group
        for i, k in enumerate(kinds))


def _kv_row_bytes(cfg: ModelConfig, kv_dtype: str) -> int:
    """K + V bytes of one token in ONE pool (the decoder-hybrid-decoder
    family's paired heads are the same count of values, fused)."""
    return 2 * cfg.num_kv_heads * cfg.resolved_head_dim \
        * _dtype_bytes(kv_dtype)


def kv_bytes_per_token(cfg: ModelConfig, kv_dtype: str = "bfloat16") -> int:
    """K + V bytes one token holds resident across the pools of
    ``--num-blocks``: every attention layer with keys of its own that sees
    every key (layers that read another layer's pool, state-space layers,
    memory units and experts hold none; a window group's pools apart)."""
    if cfg.latent_dim:
        return cfg.num_layers * cfg.latent_dim * _dtype_bytes(kv_dtype)
    return _pool_layers(cfg, 0) * _kv_row_bytes(cfg, kv_dtype)


def recurrent_state_bytes_per_slot(cfg: ModelConfig) -> int:
    """One sequence's recurrent state over the state-space layers: the
    convolution tail in the compute dtype, the state in
    ``mamba_state_dtype``."""
    tail, state = _dtype_bytes(cfg.dtype), _dtype_bytes(cfg.mamba_state_dtype)
    k = cfg.mamba_conv_kernel - 1
    d_in, n = cfg.mamba_inner_size, cfg.mamba_state_size
    return (cfg.layer_pattern.count("M") * (k * cfg.mamba_conv_dim * tail
                                            + d_in * n * state)
            + cfg.layer_pattern.count("S") * (k * d_in * tail
                                              + d_in * n * state))


def plan_training(cfg: ModelConfig, param_dtype: Optional[str] = None,
                  trainable_params: Optional[int] = None,
                  budget_bytes: int = 0, micro_batch_size: int = 0,
                  seq_len: int = 0, lora_r: int = 0) -> dict:
    """Owner-bucket prediction for one training process (no sharding —
    divide by the data/tensor-parallel factor externally). With a
    microbatch's rows and length and a budget, also the step's
    activations and how many blocks the trainer would keep under that
    budget: ``dlti_tpu.training.remat_plan.plan``, the function the
    trainer calls before its first step."""
    pbytes = _dtype_bytes(param_dtype or cfg.param_dtype)
    n = cfg.num_params()
    trainable = n if trainable_params is None else trainable_params
    owners = {
        # what is held: the published count and the held experts' pads
        "params": (n + cfg.held_pad_params) * pbytes,
        # AdamW first/second moments, fp32 regardless of param dtype.
        "optimizer_state": 2 * trainable * 4,
        # Transient but peak-relevant: one fp32 grad per trainable param.
        "grad_buffers": trainable * 4,
    }
    total = sum(owners.values())
    out = {
        "mode": "training",
        "num_params": n,
        "trainable_params": trainable,
        "owners": owners,
        "total_bytes": total,
    }
    if micro_batch_size and seq_len and budget_bytes:
        from dlti_tpu.training import remat_plan

        plan = remat_plan.plan(Config(
            model=cfg,
            lora=LoRAConfig(enabled=lora_r > 0, r=max(lora_r, 1)),
            data=DataConfig(max_seq_len=seq_len),
            train=TrainConfig(micro_batch_size=micro_batch_size)),
            total, budget_bytes)
        out["remat_plan"] = {**plan.scalars(), "line": plan.line()}
        if not plan.why_not:
            # what the step holds beside the owners above, as planned
            owners["activations"] = plan.planned_bytes - total
            total = out["total_bytes"] = plan.planned_bytes
    if budget_bytes:
        out["budget_bytes"] = budget_bytes
        out["headroom_bytes"] = budget_bytes - total
        out["fits"] = total <= budget_bytes
    return out


def plan_serving(cfg: ModelConfig, param_dtype: Optional[str] = None,
                 kv_dtype: str = "bfloat16", num_blocks: int = 256,
                 block_size: int = 16, max_model_len: int = 0,
                 max_seqs: int = 0, call_tokens: int = 2048,
                 budget_bytes: int = 0, adapter_slots: int = 0,
                 adapter_rank: int = 16,
                 adapter_targets: tuple = ("q_proj", "k_proj",
                                           "v_proj", "o_proj")) -> dict:
    """Owner-bucket prediction for one engine replica: the KV pool is
    pre-allocated at init (engine.py), so its full size is resident from
    the first request — and so is the multi-LoRA adapter pool when
    ``adapter_slots`` > 0 (hot-loads scatter into it; it never grows)."""
    pbytes = _dtype_bytes(param_dtype or cfg.param_dtype)
    n = cfg.num_params()
    per_tok = kv_bytes_per_token(cfg, kv_dtype)
    owners = {
        # what is held: the published count and the held experts' pads
        "params": (n + cfg.held_pad_params) * pbytes,
        "kv_block_pool": per_tok * block_size * num_blocks,
    }
    # What is sized by the decode slots (``max_seqs``, the engine's
    # --max-seqs): a window group's pools (ops.kv_cache.window_group_blocks;
    # ``call_tokens``: the family's widest prefill call) and the recurrent
    # state of state-space layers.
    groups = cfg.kv_group_windows
    if max_seqs and len(groups) > 1:
        from dlti_tpu.ops.kv_cache import window_group_blocks

        owners["window_block_pools"] = (
            _pool_layers(cfg, 1) * _kv_row_bytes(cfg, kv_dtype) * block_size
            * window_group_blocks(groups[1], block_size, max_seqs,
                                  call_tokens))
    if max_seqs and cfg.has_recurrent_state:
        owners["recurrent_state_pool"] = \
            max_seqs * recurrent_state_bytes_per_slot(cfg)
    if adapter_slots > 0:
        owners["lora_adapters"] = adapter_pool_bytes(
            cfg, adapter_slots, adapter_rank, adapter_targets)
    total = sum(owners.values())
    max_len = max_model_len or cfg.max_seq_len
    out = {
        "mode": "serving",
        "num_params": n,
        "owners": owners,
        "total_bytes": total,
        "kv_bytes_per_token": per_tok,
        # Block 0 is the engine's reserved trash block.
        "max_resident_tokens": (num_blocks - 1) * block_size,
        "max_seqs_at_max_len": (num_blocks - 1) * block_size // max_len,
    }
    if budget_bytes:
        out["budget_bytes"] = budget_bytes
        out["headroom_bytes"] = budget_bytes - total
        out["fits"] = total <= budget_bytes
        # How large could the pool grow inside the budget?
        kv_budget = budget_bytes - owners["params"]
        per_block = per_tok * block_size
        out["max_blocks_in_budget"] = max(0, kv_budget // per_block)
    return out


def render(p: dict) -> str:
    gib = 1024.0 ** 3
    out = [f"memory plan ({p['mode']}, {p['num_params'] / 1e6:.1f}M params)"]
    total = p["total_bytes"] or 1
    for k, v in sorted(p["owners"].items(), key=lambda kv: -kv[1]):
        out.append(f"    {k:20s} {v / gib:9.3f} GiB  {100 * v / total:5.1f}%")
    out.append(f"    {'total':20s} {total / gib:9.3f} GiB")
    if "remat_plan" in p:
        out.append("    " + p["remat_plan"]["line"])
    if "budget_bytes" in p:
        verdict = "FITS" if p["fits"] else "DOES NOT FIT"
        out.append(f"    budget {p['budget_bytes'] / gib:.2f} GiB -> "
                   f"{verdict}, headroom {p['headroom_bytes'] / gib:.3f} GiB")
    if p["mode"] == "serving":
        out.append(f"    kv/token {p['kv_bytes_per_token']} B; max resident "
                   f"tokens {p['max_resident_tokens']}; "
                   f"max seqs @ max_len {p['max_seqs_at_max_len']}")
        if "max_blocks_in_budget" in p:
            out.append(f"    pool could grow to {p['max_blocks_in_budget']} "
                       f"blocks inside the budget")
    return "\n".join(out)


def main() -> None:
    ap = argparse.ArgumentParser(
        description="static HBM capacity plan from model/engine configs")
    ap.add_argument("--model", default="llama2_7b",
                    choices=sorted(MODEL_PRESETS))
    ap.add_argument("--serving", action="store_true",
                    help="plan a serving replica instead of a trainer")
    ap.add_argument("--param-dtype", default=None,
                    help="override the preset's param storage dtype")
    ap.add_argument("--kv-dtype", default="bfloat16")
    ap.add_argument("--num-blocks", type=int, default=256)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--max-model-len", type=int, default=0)
    ap.add_argument("--max-seqs", type=int, default=0,
                    help="decode slots (engine --max-seqs): sizes a window "
                         "group's pools and the recurrent state (0 = leave "
                         "both out)")
    ap.add_argument("--lora-r", type=int, default=0,
                    help="LoRA rank: trainable = adapters only "
                         "(0 = full fine-tune)")
    ap.add_argument("--micro-batch-size", type=int, default=0,
                    help="training: rows of a microbatch on one device; "
                         "with --seq-len and --budget-gb the plan adds the "
                         "step's activations and the blocks the trainer "
                         "would keep (0 = leave them out)")
    ap.add_argument("--seq-len", type=int, default=0,
                    help="training: tokens a row (with --micro-batch-size)")
    ap.add_argument("--adapter-slots", type=int, default=0,
                    help="multi-LoRA serving pool slots (engine "
                         "--adapter-slots); adds the lora_adapters owner "
                         "(0 = off)")
    ap.add_argument("--adapter-rank", type=int, default=16,
                    help="pool rank ceiling (engine --adapter-rank)")
    ap.add_argument("--adapter-targets",
                    default="q_proj,k_proj,v_proj,o_proj",
                    help="comma-separated targeted projections")
    ap.add_argument("--budget-gb", type=float, default=0.0,
                    help="HBM budget to check the plan against")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    cfg = MODEL_PRESETS[args.model]
    budget = int(args.budget_gb * 1024 ** 3)
    if args.serving:
        p = plan_serving(cfg, param_dtype=args.param_dtype,
                         kv_dtype=args.kv_dtype, num_blocks=args.num_blocks,
                         block_size=args.block_size,
                         max_model_len=args.max_model_len,
                         max_seqs=args.max_seqs, budget_bytes=budget,
                         adapter_slots=args.adapter_slots,
                         adapter_rank=args.adapter_rank,
                         adapter_targets=tuple(
                             t.strip() for t in
                             args.adapter_targets.split(",") if t.strip()))
    else:
        trainable = (lora_trainable_params(cfg, r=args.lora_r)
                     if args.lora_r else None)
        p = plan_training(cfg, param_dtype=args.param_dtype,
                          trainable_params=trainable, budget_bytes=budget,
                          micro_batch_size=args.micro_batch_size,
                          seq_len=args.seq_len, lora_r=args.lora_r)
    if args.json:
        print(json.dumps(p, indent=2))
    else:
        print(render(p))


if __name__ == "__main__":
    main()
