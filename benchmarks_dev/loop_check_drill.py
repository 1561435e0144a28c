"""The readings behind ``serve.ouro_2_6b.short_reasoning``'s tolerances.

An engine in this process at the cell's shapes serves the check's prompts
(40, 200 and 440 tokens: each alone, then the three together), 16 greedy
tokens each, over check seeds; the cell's plain reference
(``benchmark/references/ouro_2_6b.py``, float32) gives the log-probs of the
same tokens by a full forward, and the harness's own comparison
(``benchmark/lib/serve_cell.judge`` under the cell's ``check.tolerance``)
says ``ok`` or not. For the program as stated, and for programs that are
wrong on purpose and have to come out not ``ok``:

  fp8             the weights rounded to float8_e4m3 and back
  three_passes    ``ut_steps`` 3 over the same tree
  one_cache       one entry a layer for all the passes: every pass writes
                  and reads the first pass's run of blocks
                  (``models.llama.entry_of_pass`` without its offset), what
                  a cache of ``num_layers`` entries under a looped stack does
  no_second_norm  ``sandwich_norm`` false: the tree without ``attn_out_norm``
                  and ``mlp_out_norm``, the two-norm block
  norm_outside    the final norm once, after the last pass. Not a value of
                  the configuration, so it is read the other way round: the
                  stated program's answers against a reference that is wrong
                  in this one place (built here from the reference's own
                  block and norm); the comparison is the same one

    chiprun -- bash -c 'd=benchmarks_dev/loop_check_drill.py; \\
        o=chiprun_out/readings.json; python3 $d $o --serve-fp8 && \\
        for v in fp8 stated,norm_outside three_passes one_cache \\
        no_second_norm; do python3 $d $o --variants $v; done'

A variant is a process of its own (two engines, or two sets of weights, and
the reference do not fit the chip together; ``norm_outside`` rides with
``stated``, whose answers it reads); each adds its verdicts to the output
file. ``--serve-fp8`` writes its cases beside the output for the run that
judges them. ``--tiny`` takes the cell's rehearsal stand-ins, for a try on
the CPU. PERF.md section 6, PR 49, has the readings.
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
CELL = "serve.ouro_2_6b.short_reasoning"
WRONG = ["three_passes", "one_cache", "no_second_norm", "norm_outside"]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--serve-fp8", action="store_true")
    ap.add_argument("--seeds", default="49494,1,2,3,4,5")
    ap.add_argument("--variants", default=",".join(["fp8", "stated"] + WRONG))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import serve_cell
    import spec as spec_lib
    from chip_child import model_fields
    from dlti_tpu.config import ModelConfig
    from dlti_tpu.models import LlamaForCausalLM
    from dlti_tpu.models import llama as llama_mod
    from dlti_tpu.serving.engine import EngineConfig, InferenceEngine
    from dlti_tpu.serving.sampling import SamplingParams
    from dlti_tpu.utils.platform import enable_compilation_cache

    cell = spec_lib.resolve_cell(CELL)
    config, spec = cell["config"], cell["cell"]
    if args.tiny:
        over = spec["rehearsal"]
        config = {**config,
                  "model": {**config["model"], **over["model_overrides"]}}
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

    def without_second_norms(params):
        return {**params, "model": {**params["model"], "loop": {
            name: ({k: v for k, v in layer.items()
                    if k not in ("attn_out_norm", "mlp_out_norm")}
                   if name.startswith("layers_") else layer)
            for name, layer in params["model"]["loop"].items()}}}

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
        # a stack whose outputs ignore its input passes any comparison
        print("  distinct greedy tokens over", len(cases), "answers:",
              len({t for c in cases for t in c["tokens"]}), "log-probs",
              "%.3f .. %.3f" % (
                  min(x for c in cases for x in c["server_logprobs"]),
                  max(x for c in cases for x in c["server_logprobs"])),
              flush=True)
        del eng
        gc.collect()
        return cases

    def norm_outside(params, ids):
        """The reference with the final norm once, after the last pass."""
        positions = jnp.arange(ids.shape[0])
        x = params["model"]["embed_tokens"][ids].astype(jnp.float32)
        block = jax.jit(reference._block, static_argnums=(2,))
        for _ in range(sizes["ut_steps"]):
            for i in range(sizes["num_layers"]):
                x = block(x, reference.layer_weights(params, i),
                          reference._Frozen(sizes), positions)
        x = reference._norm(x, reference._stack(params)["final_norm"]["scale"],
                            sizes["rms_norm_eps"])
        return reference._mm(x, params["lm_head"])

    def judged(cases, params, forward=None):
        """A seed: the harness's verdict over that seed's cases."""
        forward = forward or (
            lambda p, ids: reference.forward(p, sizes, ids))
        ref = []
        for c in cases:
            n, k = len(c["prompt_ids"]), len(c["tokens"])
            ids = jnp.asarray(c["prompt_ids"] + c["tokens"], jnp.int32)
            rows = jax.nn.log_softmax(forward(params, ids), -1)[n - 1:n - 1 + k]
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
    if os.path.isfile(args.out):  # a variant a process: two engines and a
        with open(args.out) as f:  # reference do not fit the chip together
            results = json.load(f)

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
        with open(fp8_file, "w") as f:
            json.dump(serve(base, to_fp8(init_params()), seeds), f)
        return
    params = init_params()
    stated_cases = None
    for name in variants:
        t0 = time.time()
        if name == "fp8":
            with open(fp8_file) as f:
                keep(name, judged(json.load(f), params), t0)
        elif name == "stated":
            stated_cases = serve(base, params, seeds)
            keep(name, judged(stated_cases, params), t0)
        elif name == "norm_outside":
            keep(name, judged(stated_cases or serve(base, params, seeds),
                              params, norm_outside), t0)
        elif name == "three_passes":
            keep(name, judged(serve(dataclasses.replace(base, ut_steps=3),
                                    params, seeds), params), t0)
        elif name == "no_second_norm":
            keep(name, judged(serve(
                dataclasses.replace(base, sandwich_norm=False),
                without_second_norms(params), seeds), params), t0)
        elif name == "one_cache":
            stated_entry = llama_mod.entry_of_pass
            llama_mod.entry_of_pass = lambda layer_cache, u, ut: layer_cache
            try:
                keep(name, judged(serve(base, params, seeds), params), t0)
            finally:
                llama_mod.entry_of_pass = stated_entry
        else:
            raise SystemExit(f"no variant {name!r}")
    for d in jax.local_devices():
        print("peak", (d.memory_stats() or {}).get("peak_bytes_in_use"))


if __name__ == "__main__":
    main()
