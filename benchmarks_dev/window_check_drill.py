"""The readings behind ``serve.kexaone_236b.mixed_lengths``'s tolerances.

An engine in this process at the cell's shapes serves the check's prompts
(96, 700 and 9,000 tokens: each alone, then the three together), 16 greedy
tokens each, over check seeds; the cell's plain reference
(``benchmark/references/kexaone_236b.py``, float32) gives the log-probs of
the same tokens by a full forward, and the harness's own comparison
(``benchmark/lib/serve_cell.judge`` under the cell's ``check.tolerance``)
says ``ok`` or not. For the program as stated, and for programs that are
wrong on purpose and have to come out not ``ok``:

  fp8           the weights rounded to float8_e4m3 and back
  no_qk_norm    no norm on queries and keys (the tree without the two norms)
  full_rotated  the full-attention layer rotated as well
  window_129    one key too many in every window layer
  freed_block   a window layer reads a released block: the decode round's
                first window column pointed at a live block of another row
                (a drill on the engine's table, not in the program)

    chiprun -- bash -c 'python3 benchmarks_dev/window_check_drill.py \\
        chiprun_out/readings.json --serve-fp8 --seeds 46464 && \\
        python3 benchmarks_dev/window_check_drill.py \\
        chiprun_out/readings.json --seeds 46464 --variants fp8,stated'

``--serve-fp8`` is a process of its own (two sets of weights do not fit the
chip): it writes its cases beside the output for the run that judges them.
``--num-blocks`` is the full group's pool (2,304 by default: the check's
prompts need 1,800; the cell's 32,768 fit as well). ``--tiny`` takes the
cell's rehearsal stand-ins, for a try on the CPU. PERF.md section 6, PR 46,
has the readings.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import random
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark", "lib")]
CELL = "serve.kexaone_236b.mixed_lengths"
WRONG = ["no_qk_norm", "full_rotated", "window_129", "freed_block"]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--serve-fp8", action="store_true")
    ap.add_argument("--seeds", default="46464,1,2,3,4,5")
    ap.add_argument("--variants", default=",".join(["fp8", "stated"] + WRONG))
    ap.add_argument("--num-blocks", type=int, default=2304)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import serve_cell
    import spec as spec_lib
    from chip_child import model_fields
    from dlti_tpu.config import ModelConfig
    from dlti_tpu.models import LlamaForCausalLM
    from dlti_tpu.serving.engine import EngineConfig, InferenceEngine
    from dlti_tpu.serving.sampling import SamplingParams
    from dlti_tpu.utils.platform import enable_compilation_cache

    cell = spec_lib.resolve_cell(CELL)
    config, spec = cell["config"], cell["cell"]
    if args.tiny:
        over = spec["rehearsal"]
        config = {**config,
                  "model": {**config["model"], **over["model_overrides"]},
                  "program": {**config["program"],
                              **over["program_overrides"]}}
        lengths, max_tokens = [20, 45, 200], 6
        ec = EngineConfig(max_seqs=4, block_size=4, num_blocks=256,
                          max_model_len=256, cache_dtype="bfloat16")
    else:
        lengths = spec["check"]["prompt_tokens"]
        max_tokens = int(spec["check"]["max_tokens"])
        ec = EngineConfig(max_seqs=32, block_size=16,
                          num_blocks=args.num_blocks, max_model_len=16384,
                          cache_dtype="bfloat16")
    tolerance = spec["check"]["tolerance"]
    seeds = [int(s) for s in args.seeds.split(",")]
    variants = args.variants.split(",")
    enable_compilation_cache()
    base = ModelConfig(**model_fields(config))
    reference = spec_lib.load_reference(config, "serve")
    sizes = reference.sizes(config)
    vocab = int(config["model"]["vocab_size"])
    print("device", jax.devices()[0], "variants", variants, "seeds", seeds,
          "tolerance", tolerance, flush=True)

    def init_params():
        return LlamaForCausalLM(base, None).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    def to_fp8(params):
        leaves, tree = jax.tree_util.tree_flatten(params)
        del params
        for i, v in enumerate(leaves):
            if v.ndim >= 2 and (args.tiny or v.dtype == jnp.bfloat16):
                leaves[i] = v.astype(jnp.float8_e4m3fn).astype(v.dtype)
        return jax.tree_util.tree_unflatten(tree, leaves)

    def without_qk_norm(params):
        body = {name: ({**layer, "attn": {
            k: v for k, v in layer["attn"].items()
            if k not in ("q_norm", "k_norm")}}
            if name.startswith("layers_") else layer)
            for name, layer in params["model"].items()}
        return {**params, "model": body}

    def prompts_of(seed):
        rng = random.Random(seed)
        return [[1] + [rng.randrange(3, vocab) for _ in range(n - 1)]
                for n in lengths]

    def serve(cfg, params, seed_list, drill=False):
        """The cases of ``seed_list`` as the harness's ``judge`` takes them,
        a seed in the key."""
        eng = InferenceEngine(cfg, params, ec)
        if drill:
            cover = eng._window_cover

            def bad_cover(slot, first, upto):
                cover(slot, first, upto)
                if slot.window_first > 0 and slot.window_blocks:
                    others = [b for s in eng.slots if s is not slot
                              for b in s.window_blocks]
                    eng._window_tables[slot.slot_id, 0] = \
                        others[0] if others else slot.window_blocks[-1]

            eng._window_cover = bad_cover
        greedy = SamplingParams(temperature=0.0, max_tokens=max_tokens)
        cases = []
        for seed in seed_list:
            asked, t0 = prompts_of(seed), time.time()
            alone = [eng.generate([p], greedy)[0] for p in asked]
            busy = eng.generate(asked, greedy)
            for how, results in (("alone", alone), ("busy", busy)):
                for i, res in enumerate(results):
                    cases.append({"key": f"{seed}/{how}/{i}",
                                  "prompt_ids": asked[i],
                                  "tokens": res.output_token_ids,
                                  "server_logprobs": res.output_logprobs})
            print("  served seed", seed, "%.1f s" % (time.time() - t0),
                  flush=True)
        freed = {str(k): v for k, v in eng.kv_freed.items()}
        del eng
        gc.collect()
        return cases, freed

    log_probs = jax.jit(lambda params, ids: jax.nn.log_softmax(
        reference.forward(params, sizes, ids), -1))

    def judged(cases, params):
        """A seed: the harness's verdict over that seed's cases."""
        ref = []
        for c in cases:
            n, k = len(c["prompt_ids"]), len(c["tokens"])
            ids = jnp.asarray(c["prompt_ids"] + c["tokens"], jnp.int32)
            rows = log_probs(params, jnp.pad(
                ids, (0, (-ids.shape[0]) % 64)))[n - 1:n - 1 + k]
            ref.append({"key": c["key"],
                        "logprobs": [float(x) for x in rows[
                            jnp.arange(k), jnp.asarray(c["tokens"])]],
                        "best_logprobs": [float(x) for x in rows.max(-1)]})
        out = {}
        for seed in sorted({c["key"].split("/")[0] for c in cases}):
            out[seed] = serve_cell.judge(
                [c for c in cases if c["key"].startswith(seed + "/")],
                {"cases": ref}, tolerance)
        return out

    results = {}

    def keep(name, verdicts, t0):
        results[name] = verdicts
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(name, "%.0f s" % (time.time() - t0), {
            seed: (round(v["max_abs_logprob_diff"], 4),
                   round(v["max_greedy_gap"], 4), "ok" if v["ok"] else
                   "NOT ok") for seed, v in verdicts.items()}, flush=True)

    fp8_file = args.out + ".fp8_cases.json"
    if args.serve_fp8:
        cases, _ = serve(base, to_fp8(init_params()), seeds)
        with open(fp8_file, "w") as f:
            json.dump(cases, f)
        return
    params = init_params()
    two = seeds[:2]
    plan = {
        "stated": (base, params, seeds, False),
        "no_qk_norm": (dataclasses.replace(base, qk_norm=False),
                       without_qk_norm(params), two, False),
        "full_rotated": (dataclasses.replace(base, rope_on_full_layers=True),
                         params, two, False),
        "window_129": (dataclasses.replace(base, layer_windows=tuple(
            w and w + 1 for w in base.layer_windows)), params, two, False),
        "freed_block": (base, params, two, True)}
    for name in variants:
        t0 = time.time()
        if name == "fp8":
            with open(fp8_file) as f:
                keep(name, judged(json.load(f), params), t0)
            continue
        cfg, served_with, seed_list, drill = plan[name]
        cases, freed = serve(cfg, served_with, seed_list, drill)
        results[name + "_freed"] = freed
        keep(name, judged(cases, params), t0)
    for d in jax.local_devices():
        print("peak", (d.memory_stats() or {}).get("peak_bytes_in_use"))


if __name__ == "__main__":
    main()
