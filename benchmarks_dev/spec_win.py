"""Adaptive speculative decoding A/B: favorable AND adversarial traces.

Methodology fixes over the r03 version (whose committed artifact
recorded a 0.103 "speedup"): the measured window previously included
XLA compiles — run 1 of the plain arm compiled the decode ladder
mid-measurement and the spec arm compiled a fresh draft-length rung
mid-run-2, so the medians compared compile time, not decode time. Every
arm now runs its FULL measured workload once before timing (compiling
prefill buckets, the decode ladder, and every spec-k rung the per-slot
controller will visit), reports the median of >= 3 measured runs, and
asserts byte-identical outputs against the plain-greedy reference
before a single number is written.

Traces:

* **favorable** — prompts whose greedy continuation locks into a short
  loop (repetitive/code-template shape): the n-gram proposer gets long
  accepted prefixes and the ladder stays at the top rung.
* **adversarial** — prompts whose continuation wanders: near-zero
  acceptance, so the per-slot gate pauses speculation and the ladder
  collapses toward k=1; the claim is bounded overhead, not a win.

Usage:
  python benchmarks_dev/spec_win.py --cpu            # llama_tiny check
  python benchmarks_dev/spec_win.py                  # real chip, export
  python benchmarks_dev/spec_win.py --cpu --runs 1 --max-tokens 48 \
      --json-out /tmp/x.json                         # CI smoke shape
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
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--export", default="exports/glaive_300m")
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--max-tokens", type=int, default=160)
    ap.add_argument("--draft", type=int, default=6)
    ap.add_argument("--json-out", default="")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from dlti_tpu.config import MODEL_PRESETS
    from dlti_tpu.models import LlamaForCausalLM
    from dlti_tpu.serving.engine import (
        EngineConfig, InferenceEngine, SamplingParams,
    )

    if args.cpu:
        cfg = dataclasses.replace(MODEL_PRESETS["llama_tiny"],
                                  dtype="float32", param_dtype="float32")
        model = LlamaForCausalLM(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        tok = None
    else:
        from dlti_tpu.checkpoint.export import load_exported_model
        from dlti_tpu.data import ByteTokenizer

        params, full_cfg = load_exported_model(args.export)
        cfg = full_cfg.model
        tok = ByteTokenizer()

    if tok is None:
        # llama_tiny's greedy continuation of [6,6,7,7,...] is a
        # period-1 loop (prompt-lookup heaven); the adversarial prompts
        # wander through distinct tokens for many rounds.
        favorable = [([6, 6, 7, 7] * 4)[: 8 + i] for i in range(4)]
        adversarial = [[2, 7, 1, 8, 2, 8], [11, 13, 17, 19, 23],
                       [10, 20, 30, 40, 50, 60], [19, 28, 37, 46, 55]]
    else:
        block = ("def check_{i}(value):\n"
                 "    if value is None:\n"
                 "        return default\n"
                 "    return transform(value)\n\n")
        favorable = [tok.encode("".join(
            block.replace("{i}", str(i)) for i in range(4)))[:512]
            for _ in range(4)]
        prose = ("the quarterly throughput review considered seventeen "
                 "distinct mitigation strategies across regions, none "
                 "repeated verbatim anywhere in the corpus; ")
        adversarial = [tok.encode(prose * (3 + i))[:256] for i in range(4)]

    def build(spec: bool):
        ec = EngineConfig(
            max_seqs=4, block_size=16,
            num_blocks=max(256, (args.max_tokens + 600) // 16 * 8),
            max_model_len=1024, eos_token_id=-1,
            cache_dtype="float32" if args.cpu else "bfloat16",
            speculative="ngram" if spec else "none",
            num_draft_tokens=args.draft,
        )
        return InferenceEngine(cfg, params, ec)

    sp = SamplingParams(temperature=0.0, max_tokens=args.max_tokens)

    def measure(spec: bool, prompts):
        eng = build(spec)
        # Compile warmup OUTSIDE the measured window: the decode ladder,
        # prefill buckets, and — by running the full measured workload
        # once — every spec-k rung the adaptive controller will visit.
        eng.warmup_decode_ladder()
        eng.generate(prompts, sp)
        rates, toks = [], None
        for _ in range(args.runs):
            t0 = time.perf_counter()
            res = eng.generate(prompts, sp)
            dt = time.perf_counter() - t0
            n = sum(len(r.output_token_ids) for r in res)
            rates.append(n / dt)
            run_toks = [r.output_token_ids for r in res]
            assert toks is None or run_toks == toks, "non-deterministic run"
            toks = run_toks
        return rates, toks, dict(eng.stats)

    def trace(name, prompts):
        plain_rates, plain_toks, _ = measure(False, prompts)
        spec_rates, spec_toks, st = measure(True, prompts)
        # Per-arm outputs-equal assert BEFORE any number is reported.
        assert spec_toks == plain_toks, \
            f"{name}: speculation changed greedy outputs"
        med_p = statistics.median(plain_rates)
        med_s = statistics.median(spec_rates)
        acc = (st["spec_accepted"] / st["spec_proposed"]
               if st.get("spec_proposed") else 0.0)
        return {
            "plain_tok_s_all": [round(r, 1) for r in plain_rates],
            "spec_tok_s_all": [round(r, 1) for r in spec_rates],
            "plain_tok_s_median": round(med_p, 1),
            "spec_tok_s_median": round(med_s, 1),
            "speedup": round(med_s / med_p, 3),
            "draft_acceptance": round(acc, 3),
            "spec_paused_rounds": st.get("spec_paused_rounds", 0),
            "outputs_equal": True,
        }

    out = {
        "what": "adaptive speculation (per-slot gate + draft-length "
                "ladder) vs plain decode, "
                "on favorable AND adversarial traces",
        "platform": "cpu/llama_tiny" if args.cpu else f"tpu/{args.export}",
        # (a decode round is one step; the key stays so that the records
        # under results/ compare)
        "steps_per_sync": 1, "num_draft_tokens": args.draft,
        "max_tokens": args.max_tokens, "runs": args.runs,
        "favorable": trace("favorable", favorable),
        "adversarial": trace("adversarial", adversarial),
        "date": time.strftime("%Y-%m-%d"),
    }
    out["outputs_equal"] = (out["favorable"]["outputs_equal"]
                            and out["adversarial"]["outputs_equal"])
    name = args.json_out or ("results/spec_adaptive_cpu.json" if args.cpu
                             else "results/spec_adaptive.json")
    with open(name, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
