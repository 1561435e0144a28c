"""The readings behind ``train.jamba2_3b.long_doc_sft``'s tolerances.

The harness's own training check (``benchmark/lib/check.py``:
``train_inputs``, ``program_side``, ``compare_train`` under the cell's
``check.tolerance``) on one packed row of the cell's check, over check
seeds: the cell's plain reference (``benchmark/references/jamba2_3b.py``,
float32, every document by itself) against the program as stated, and
against programs that are wrong on purpose, each of which has to fail at
least one limit:

  no_reset        the scan's state carried across document starts (a
                  document reads its neighbour's state)
  no_inner_norms  the RMSNorms on dt, B and C dropped
  bf16_state      the scan and its state in bfloat16
  fp8             the base weights rounded to float8_e4m3 (a scale an output
                  channel) and back; LAST of a seed's variants: the rounded
                  tree takes the stated one's place (two sets of weights
                  and a row's activations do not fit the chip together)

    chiprun --timeout 3000 -- python3 benchmarks_dev/jamba_check_drill.py \\
        chiprun_out/jamba_check.json --seeds 20261005,1,2

One process: the reference's side of a seed is computed once and every
variant is held against it. ``--tiny`` takes the cell's rehearsal stand-ins,
for a try on the CPU. PERF.md section 6, PR 56, has the readings.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark", "lib")]
CELL = "train.jamba2_3b.long_doc_sft"
VARIANTS = ("stated", "no_reset", "no_inner_norms", "bf16_state", "fp8")


def _bf16_scan(u, dt, a, b_in, c_in, keep):
    """The recurrence with its inputs, its state and its outputs in
    bfloat16 (autodiff through checkpointed chunks of 128 tokens)."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    rows, length, d = u.shape
    pad = -length % 128

    def lay(t, fill=0.0):
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2),
                    constant_values=fill).astype(bf)
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((-1, 128) + t.shape[1:])

    a = a.astype(bf)

    def step(s, x):
        u_t, dt_t, b_t, c_t, keep_t = x
        s = jnp.exp(dt_t[..., None] * a) * keep_t[:, None, None] * s \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], -1)

    chunk = jax.checkpoint(lambda s, x: jax.lax.scan(step, s, x))
    _, y = jax.lax.scan(chunk, jnp.zeros((rows, d, a.shape[1]), bf),
                        (lay(u), lay(dt), lay(b_in), lay(c_in),
                         lay(keep, 1.0)))
    y = jnp.moveaxis(y.reshape((-1,) + y.shape[2:]), 0, 1)[:, :length]
    return y.astype(jnp.float32)


def _fp8(params):
    """Base kernels through float8_e4m3fn and back, a scale a column."""
    import jax
    import jax.numpy as jnp

    def rounded(path, v):
        if getattr(path[-1], "key", None) != "kernel":
            return v
        w = v.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0
        scale = jnp.where(scale > 0, scale, 1.0)
        return ((w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
                * scale).astype(v.dtype)

    return jax.tree_util.tree_map_with_path(rounded, params)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--seeds", default="20261005")
    ap.add_argument("--variants", default=",".join(VARIANTS))
    args = ap.parse_args()

    import jax
    import numpy as np

    import check
    import harness
    import spec as spec_lib
    from dlti_tpu.models import build_model, mamba1

    cell = spec_lib.resolve_cell(CELL)
    config, spec = dict(cell["config"]), dict(cell["cell"])
    if args.tiny:
        spec = harness.overlay(spec, spec["rehearsal"])
        for part in ("model", "program"):
            config[part] = {**config[part], **spec[part + "_overrides"]}
    check_spec = dict(spec["check"])
    reference = spec_lib.load_reference(config, "train")
    sizes = reference.sizes(config)
    stated_scan = mamba1.chunked_selective_scan
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        check_spec["seed"] = seed
        model, params, batch = check.train_inputs(config, check_spec)
        t0 = time.time()
        loss, grads, picked = reference.grad(
            params, sizes, batch, check.LORA_SCALING, check.is_lora)
        ref = {"reference_loss": loss, "device": check._device()}
        arrays = {"token_logprobs": np.asarray(picked)}
        for name, g in zip(check._leaf_names(grads),
                           jax.tree_util.tree_leaves(grads)):
            arrays["grad:" + name] = np.asarray(g, np.float32)
        del grads, picked
        docs = [len(reference.documents(batch["segment_ids"][r]))
                for r in range(batch["segment_ids"].shape[0])]
        print(f"seed {seed}: reference {time.time() - t0:.0f} s, loss "
              f"{loss:.5f}, documents a row {docs}", flush=True)
        for variant in args.variants.split(","):
            mamba1.chunked_selective_scan = stated_scan
            v_model, v_params = model, params
            if variant == "no_reset":
                mamba1.chunked_selective_scan = \
                    lambda u, dt, a, b, c, keep: stated_scan(
                        u, dt, a, b, c, jax.numpy.ones_like(keep))
            elif variant == "bf16_state":
                mamba1.chunked_selective_scan = _bf16_scan
            elif variant == "no_inner_norms":
                v_model = build_model(dataclasses.replace(
                    model.cfg, mamba_inner_norms=False), model.lora)
            elif variant == "fp8":
                params = v_params = _fp8(params)
            t0 = time.time()
            out = check.compare_train(
                *check.program_side(v_model, v_params, batch), ref, arrays,
                batch["loss_mask"], check_spec["tolerance"])
            row = {"seed": seed, "variant": variant,
                   "seconds": round(time.time() - t0, 1),
                   **{k: out[k] for k in (
                       "ok", "loss_abs_diff", "token_logprob_rms_diff",
                       "token_logprob_max_diff", "grad_norm_ratio",
                       "grad_cosine", "grad_rel_diff")}}
            rows.append(row)
            print(json.dumps(row), flush=True)
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                json.dump({"tolerance": check_spec["tolerance"],
                           "rows": rows}, f, indent=1)
    mamba1.chunked_selective_scan = stated_scan


if __name__ == "__main__":
    main()
