"""int8 KV cache A/B at FIXED KV HBM.

The claim to prove (or honestly demote): halving KV bytes buys double
the decode slots, which buys throughput. Both arms get the SAME KV pool
byte budget; the int8 arm spends it on 2x the slots:

  A: bf16 KV, 20 slots,  N blocks
  B: int8 KV, 40 slots, 2N blocks  (same bytes: int8 = half + scales)

Engine-direct (no server/link noise in scheduling), deep queue, greedy,
fixed-length outputs, >= 3 repeats per arm, all runs reported.

  python benchmarks_dev/int8_kv_ab.py --export exports/glaive_7b_r05
  python benchmarks_dev/int8_kv_ab.py --cpu          # mechanism check
"""

import argparse
import json
import os
import statistics
import sys
import time

_repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _repo)
os.chdir(_repo)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--export", default="exports/glaive_7b_r05")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--requests", type=int, default=112)
    ap.add_argument("--max-tokens", type=int, default=256)
    ap.add_argument("--json-out", default="",
                    help="output path (default: results/int8_kv_ab_{cpu,r05}.json)")
    ap.add_argument("--blocks", type=int, default=455,
                    help="bf16-arm block count (int8 arm gets 2x)")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import dataclasses
    import jax.numpy as jnp

    from dlti_tpu.serving.engine import (
        EngineConfig, InferenceEngine, SamplingParams,
    )

    if args.cpu:
        from dlti_tpu.config import MODEL_PRESETS
        from dlti_tpu.models import LlamaForCausalLM

        cfg = dataclasses.replace(MODEL_PRESETS["llama_tiny"],
                                  dtype="float32", param_dtype="float32")
        params = LlamaForCausalLM(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        lora = None
        quant = False
        args.requests, args.max_tokens, args.blocks = 24, 32, 64
        slots_a, slots_b = 4, 8
    else:
        from dlti_tpu.checkpoint.export import load_exported_model
        from dlti_tpu.models.quantization import quantize_params_int8

        params, full_cfg = load_exported_model(args.export)
        cfg = full_cfg.model
        lora = full_cfg.lora if full_cfg.lora.enabled else None
        params = quantize_params_int8(params, donate=True)  # int8 weights
        quant = True
        slots_a, slots_b = 20, 40

    prompt_base = list(range(5, 69))  # 64-token prompt

    def measure(kv_dtype, slots, blocks):
        ec = EngineConfig(
            max_seqs=slots, block_size=16, num_blocks=blocks,
            max_model_len=512, eos_token_id=-1,
            cache_dtype=kv_dtype if not args.cpu else (
                "int8" if kv_dtype == "int8" else "float32"))
        eng = InferenceEngine(cfg, params, ec, lora)
        sp = SamplingParams(temperature=0.0, max_tokens=args.max_tokens)
        # compile warmup
        eng.generate([prompt_base[:8]], SamplingParams(temperature=0.0,
                                                       max_tokens=2))
        eng.warmup_decode_ladder()
        rates = []
        for r in range(args.runs):
            prompts = [prompt_base[: 16 + (i % 48)]
                       for i in range(args.requests)]
            t0 = time.perf_counter()
            res = eng.generate(prompts, sp)
            dt = time.perf_counter() - t0
            n = sum(len(x.output_token_ids) for x in res)
            rates.append(round(n / dt, 1))
            print(f"  {kv_dtype}@{slots}: run {r}: {rates[-1]} tok/s",
                  flush=True)
        st = dict(eng.stats)
        occ = st["decode_slot_steps"] / max(1, slots * st["decode_steps"])
        del eng
        return rates, round(occ, 4)

    a_rates, a_occ = measure("bfloat16", slots_a, args.blocks)
    b_rates, b_occ = measure("int8", slots_b, args.blocks * 2)

    med_a, med_b = statistics.median(a_rates), statistics.median(b_rates)
    out = {
        "what": "int8 KV A/B at fixed KV pool bytes: bf16 KV with S slots "
                "vs int8 KV (half bytes/token + fp32 scales) with 2S slots "
                "and 2x blocks; engine-direct deep queue, greedy, "
                "fixed-length outputs",
        "platform": "cpu/llama_tiny" if args.cpu else f"tpu/{args.export}",
        "arm_a": {"kv": "bfloat16", "slots": slots_a, "blocks": args.blocks,
                  "runs_tok_s": a_rates, "median": med_a, "occupancy": a_occ},
        "arm_b": {"kv": "int8", "slots": slots_b, "blocks": args.blocks * 2,
                  "runs_tok_s": b_rates, "median": med_b, "occupancy": b_occ},
        "speedup_b_over_a": round(med_b / med_a, 3),
        "int8_weights": quant,
        # (a decode round is one step; the key stays so that the records
        # under results/ compare)
        "steps_per_sync": 1, "max_tokens": args.max_tokens,
        "requests": args.requests, "date": "2026-08-01",
    }
    name = args.json_out or ("results/int8_kv_ab_cpu.json" if args.cpu
                             else "results/int8_kv_ab_r05.json")
    with open(name, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
