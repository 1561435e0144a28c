"""The readings behind ``serve.phi4_mini_flash.reasoning_turns``'s tolerances.

An engine in this process at the cell's shapes serves the check's prompts
(96, 300 and 700 tokens: each alone, then the three together), 16 greedy
tokens each, over check seeds; the cell's plain reference
(``benchmark/references/phi4_mini_flash.py``, float32) gives the log-probs of
the same tokens by a full forward, and the harness's own comparison
(``benchmark/lib/serve_cell.judge`` under the cell's ``check.tolerance``)
says ``ok`` or not. For the program as stated, and for programs that are
wrong on purpose:

  fp8          the weights rounded to float8_e4m3 and back (has to fail)
  bf16_state   the recurrent state kept in bfloat16 (``mamba_state_dtype``):
               can it be told from float32?
  no_window    every attention layer over every key (``layer_windows`` all
               0): the 700-token prompt is past the window
  no_memory    the gated memory units' output projections zeroed (has to
               fail: a program that dropped the memory's path)

    chiprun -- bash -c 'd=benchmarks_dev/sambay_check_drill.py; \\
        o=chiprun_out/readings.json; python3 $d $o --serve fp8 && \\
        python3 $d $o --serve no_memory && \\
        for v in stated,fp8,no_memory bf16_state no_window; do \\
        python3 $d $o --variants $v; done'

A served variant is a process of its own (two sets of weights, or an engine
and the reference's float32 copies, do not fit the chip together); ``--serve
NAME`` writes that variant's cases beside the output for the run that judges
them against the stated weights. ``--tiny`` takes the cell's rehearsal
stand-ins, for a try on the CPU. It prints how many distinct greedy tokens
the answers hold: READ THAT LINE (a stack whose outputs ignore its input
passes any comparison). PERF.md section 6, PR 53, has the readings.
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
CELL = "serve.phi4_mini_flash.reasoning_turns"
# variants whose WEIGHTS differ: served in a process of their own
OTHER_WEIGHTS = ("fp8", "no_memory")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--serve", default="", choices=("",) + OTHER_WEIGHTS)
    ap.add_argument("--seeds", default="53535,1,2,3,4,5")
    ap.add_argument("--variants", default="stated")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import serve_cell
    import spec as spec_lib
    from chip_child import model_fields
    from dlti_tpu.config import ModelConfig
    from dlti_tpu.models import build_model
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
        spec = {**spec, "args": {**spec["args"], **over["args"]},
                "check": {**spec["check"], **over["check"]}}
    a = spec["args"]
    ec = EngineConfig(
        max_seqs=int(a["--max-seqs"]), block_size=int(a["--block-size"]),
        num_blocks=int(a["--num-blocks"]),
        max_model_len=int(a["--max-model-len"]),
        cache_dtype=a["--kv-cache-dtype"])
    lengths = spec["check"]["prompt_tokens"]
    max_tokens = int(spec["check"]["max_tokens"])
    tolerance = spec["check"]["tolerance"]
    seeds = [int(s) for s in args.seeds.split(",")]
    enable_compilation_cache()
    base = ModelConfig(**model_fields(config))
    reference = spec_lib.load_reference(config, "serve")
    sizes = reference.sizes(config)
    vocab = int(config["model"]["vocab_size"])
    print("device", jax.devices()[0], "serve", args.serve, "variants",
          args.variants, "seeds", seeds, "tolerance", tolerance, flush=True)

    def init_params():
        return build_model(base).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    def to_fp8(params):
        leaves, tree = jax.tree_util.tree_flatten(params)
        del params
        for i, v in enumerate(leaves):
            if v.ndim >= 2 and (args.tiny or v.dtype == jnp.bfloat16):
                leaves[i] = v.astype(jnp.float8_e4m3fn).astype(v.dtype)
        return jax.tree_util.tree_unflatten(tree, leaves)

    def no_memory(params):
        out = dict(params)
        for i, kind in enumerate(base.layer_pattern):
            if kind == "G":
                layer = dict(out[f"layers_{i}"])
                mixer = dict(layer["mixer"])
                mixer["out_proj"] = jax.tree_util.tree_map(
                    jnp.zeros_like, mixer["out_proj"])
                layer["mixer"] = mixer
                out[f"layers_{i}"] = layer
        return out

    def prompts_of(seed):
        rng = random.Random(seed)
        return [[1] + [rng.randrange(3, vocab) for _ in range(n - 1)]
                for n in lengths]

    def serve(cfg, params, seed_list):
        """The cases of ``seed_list`` as the harness's ``judge`` takes them,
        a seed in the key."""
        eng = InferenceEngine(cfg, params, ec)
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
        print("  distinct greedy tokens over", len(cases), "answers:",
              len({t for c in cases for t in c["tokens"]}), "of",
              sum(len(c["tokens"]) for c in cases), "; tokens equal to the "
              "token before:", sum(
                  x == y for c in cases for x, y in zip(
                      c["tokens"], [c["prompt_ids"][-1]] + c["tokens"])),
              "; log-probs %.3f .. %.3f" % (
                  min(x for c in cases for x in c["server_logprobs"]),
                  max(x for c in cases for x in c["server_logprobs"])),
              flush=True)
        del eng
        gc.collect()
        return cases

    def judged(cases, params):
        """A seed: the harness's verdict over that seed's cases."""
        forward = jax.jit(lambda p, ids: jax.nn.log_softmax(
            reference.forward(p, sizes, ids), -1))
        ref = []
        for c in cases:
            n, k = len(c["prompt_ids"]), len(c["tokens"])
            ids = jnp.asarray(c["prompt_ids"] + c["tokens"], jnp.int32)
            pad = (-ids.shape[0]) % 64
            rows = forward(params, jnp.pad(ids, (0, pad)))[n - 1:n - 1 + k]
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
    if os.path.isfile(args.out):
        with open(args.out) as f:
            results = json.load(f)

    def keep(name, verdicts, t0):
        results[name] = verdicts
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(name, "%.0f s" % (time.time() - t0), {
            seed: (round(v["max_abs_logprob_diff"], 4),
                   round(v["max_greedy_gap"], 4), "ok" if v["ok"] else
                   "NOT ok") for seed, v in verdicts.items()}, flush=True)

    def cases_file(name):
        return f"{args.out}.{name}_cases.json"

    if args.serve:
        wrong = {"fp8": to_fp8, "no_memory": no_memory}[args.serve]
        with open(cases_file(args.serve), "w") as f:
            json.dump(serve(base, wrong(init_params()), seeds), f)
        return
    params = init_params()
    for name in args.variants.split(","):
        t0 = time.time()
        if name in OTHER_WEIGHTS:
            with open(cases_file(name)) as f:
                keep(name, judged(json.load(f), params), t0)
        elif name == "stated":
            keep(name, judged(serve(base, params, seeds), params), t0)
        elif name == "bf16_state":
            keep(name, judged(serve(dataclasses.replace(
                base, mamba_state_dtype="bfloat16"), params, seeds),
                params), t0)
        elif name == "no_window":
            keep(name, judged(serve(dataclasses.replace(
                base, layer_windows=(0,) * base.num_layers), params, seeds),
                params), t0)
        else:
            raise SystemExit(f"no variant {name!r}")
    for d in jax.local_devices():
        print("peak", (d.memory_stats() or {}).get("peak_bytes_in_use"))


if __name__ == "__main__":
    main()
