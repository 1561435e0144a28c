"""Correctness against the plain reference, in processes of their own.

A chip belongs to one process at a time, so nothing here runs beside the
trainer or the server: it runs after they have exited. Only what the
*reference* computed is kept in a file next to the compile cache
(``.bench_cache/checks/``), keyed by everything it depends on - it is a
function of the configuration and the seeded inputs alone. Whatever the
*program* computes is computed again in every run, so that ``correct`` is
always about the code this run measured.

    check.py train-reference --model-file F --spec F --out F
        float32 loss, per-token log-probs and LoRA gradients of seeded
        packed rows (kept: F and F.npz)
    check.py train-program --model-file F --spec F --reference F --out F
        the same through the program's model, loss function and training
        attention path, held against that file (every run)
    check.py serve --model-file F --cases F --out F
        reference log-probs of the tokens the server chose for seeded
        prompts (full forward, no cache), for the harness to hold the
        server's own log-probs against (kept, keyed by those tokens)

The weights are the program's own initialisation from a fixed key (the
server's ``--random-init`` uses ``PRNGKey(0)``), so both sides hold the
same ones without a file changing hands.

Which reference and which constructor of the program's model, the
configuration file says (``spec.load_reference``, ``spec.program_model``);
the reference takes its sizes from that file, the program from its own
``ModelConfig``.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import zlib

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.getcwd())


import spec as spec_lib  # noqa: E402


def _configuration(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _program_model(config: dict, lora):
    """The program's model as the configuration names its constructor,
    built from the program's own translation of the file."""
    import importlib

    from chip_child import model_fields
    from dlti_tpu.config import ModelConfig

    module, attr = spec_lib.program_model(config)
    build = getattr(importlib.import_module(module), attr)
    return build(ModelConfig(**model_fields(config)), lora)


def _enable_cache() -> None:
    from dlti_tpu.utils.platform import enable_compilation_cache

    enable_compilation_cache()


def packed_rows(rows: int, seq_len: int, vocab: int, seed: int,
                median: int) -> dict:
    """Seeded rows of back-to-back documents: ids, loss mask, per-document
    positions and 1-based segment ids (0 = padding), as numpy arrays."""
    import numpy as np

    rng = random.Random(seed)
    ids = np.zeros((rows, seq_len), np.int32)
    seg = np.zeros((rows, seq_len), np.int32)
    pos = np.zeros((rows, seq_len), np.int32)
    for r in range(rows):
        at, doc = 0, 0
        while at < seq_len - 8:  # leave a few pad positions in every row
            n = min(max(8, int(rng.lognormvariate(0, 0.8) * median)),
                    seq_len - 8 - at)
            if n < 2:
                break
            doc += 1
            ids[r, at:at + n] = [rng.randrange(3, vocab) for _ in range(n)]
            seg[r, at:at + n] = doc
            pos[r, at:at + n] = np.arange(n)
            at += n
    return {"input_ids": ids, "loss_mask": (seg > 0).astype(np.int32),
            "positions": pos, "segment_ids": seg}


def _tree_norm(tree) -> float:
    import jax
    import jax.numpy as jnp

    return float(jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in jax.tree_util.tree_leaves(tree))))


def train_inputs(config: dict, spec: dict):
    """(the program's model, seeded parameters, seeded packed rows): what
    both sides of the training check compute on."""
    import jax
    import jax.numpy as jnp

    from dlti_tpu.config import LoRAConfig

    _enable_cache()
    r = int(spec["lora_r"])
    model = _program_model(config,
                           LoRAConfig(enabled=True, r=r, alpha=2 * r))
    key = jax.random.PRNGKey(int(spec["seed"]))
    params = jax.jit(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32))["params"])(key)

    # LoRA's B starts at zero, which would make A's gradient zero and the
    # adapters invisible in the loss: give B seeded values of A's scale.
    def perturb(path, v):
        if getattr(path[-1], "key", None) != "lora_b":
            return v
        k = jax.random.fold_in(key, zlib.crc32(str(path).encode()))
        return (0.02 * jax.random.normal(k, v.shape)).astype(v.dtype)

    params = jax.tree_util.tree_map_with_path(perturb, params)
    batch = {k: jnp.asarray(v) for k, v in packed_rows(
        int(spec["rows"]), int(spec["seq_len"]),
        int(config["model"]["vocab_size"]), int(spec["seed"]),
        int(spec["doc_median"])).items()}
    return model, params, batch


LORA_SCALING = 2.0  # alpha / r of the adapters train_inputs builds


def is_lora(path) -> bool:
    """The leaves the fine-tune trains, in the tree train_inputs builds."""
    return any(getattr(k, "key", None) in ("lora_a", "lora_b") for k in path)


def _leaf_names(tree) -> list:
    import jax

    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _device() -> dict:
    import jax

    return {"platform": jax.devices()[0].platform,
            "kind": jax.devices()[0].device_kind}


def train_reference(args) -> dict:
    """The reference's side, written once per (configuration, check)."""
    import jax
    import numpy as np

    with open(args.spec) as f:
        spec = json.load(f)
    config = _configuration(args.model_file)
    reference = spec_lib.load_reference(config, "train")
    _, params, batch = train_inputs(config, spec)
    loss, grads, picked = reference.grad(params, reference.sizes(config),
                                         batch, LORA_SCALING, is_lora)
    arrays = {"token_logprobs": np.asarray(picked)}
    for name, g in zip(_leaf_names(grads),
                       jax.tree_util.tree_leaves(grads)):
        arrays["grad:" + name] = np.asarray(g, np.float32)
    np.savez(args.out + ".npz", **arrays)
    return {"reference_loss": loss, "reference_grad_norm": _tree_norm(grads),
            "arrays": os.path.basename(args.out) + ".npz",
            "device": _device()}


def program_side(model, params, batch):
    """(loss, per-token log-probs of the targets, LoRA gradient tree) of
    the program: its model in training mode (no cache -> the training
    attention path, remat as configured), its loss function, its dtypes -
    the calls ``make_train_step`` makes for a microbatch."""
    import jax
    import jax.numpy as jnp

    from dlti_tpu.training.step import causal_lm_loss

    # Two trees of one shape, with None where the other holds the leaf.
    trainable = jax.tree_util.tree_map_with_path(
        lambda p, v: v if is_lora(p) else None, params)
    frozen = jax.tree_util.tree_map_with_path(
        lambda p, v: None if is_lora(p) else v, params)

    def merge(trainable, frozen):
        return jax.tree_util.tree_map(
            lambda a, b: b if a is None else a, trainable, frozen,
            is_leaf=lambda v: v is None)

    def program_loss(trainable, frozen, batch):
        logits, _ = model.apply(
            {"params": merge(trainable, frozen)},
            batch["input_ids"], positions=batch["positions"],
            segment_ids=batch["segment_ids"], deterministic=True)
        total, count = causal_lm_loss(logits, batch["input_ids"],
                                      batch["loss_mask"])
        shifted = logits[:, :-1].astype(jnp.float32)
        picked = jnp.take_along_axis(
            shifted, batch["input_ids"][:, 1:, None], axis=-1)[..., 0] \
            - jax.nn.logsumexp(shifted, axis=-1)
        return total / count, picked

    (loss, picked), grads = jax.jit(jax.value_and_grad(
        program_loss, has_aux=True))(trainable, frozen, batch)
    return float(loss), picked, grads


def compare_train(p_loss, p_picked, p_grads, ref: dict, arrays, mask,
                  tol: dict) -> dict:
    """The program's side held against the reference's file."""
    import jax
    import numpy as np

    names = _leaf_names(p_grads)
    leaves = [np.asarray(g, np.float32)
              for g in jax.tree_util.tree_leaves(p_grads)]
    groups: dict = {}
    dot = pp = rr = dd = 0.0
    for name, gp in zip(names, leaves):
        gr = arrays["grad:" + name]
        parts = [k for k in name.split("/") if not k.startswith("layers_")
                 and k not in ("model", "attn", "mlp")]
        acc = groups.setdefault("/".join(parts), [0.0, 0.0, 0.0])
        sums = (float((gp * gp).sum()), float((gr * gr).sum()),
                float(((gp - gr) ** 2).sum()))
        for i, v in enumerate(sums):
            acc[i] += v
        dot += float((gp * gr).sum())
        pp, rr, dd = pp + sums[0], rr + sums[1], dd + sums[2]
    w = np.asarray(mask)[:, 1:].astype(np.float32)
    err = (np.asarray(p_picked, np.float32) - arrays["token_logprobs"]) * w
    out = {
        "program_loss": p_loss, "reference_loss": ref["reference_loss"],
        "loss_abs_diff": abs(p_loss - ref["reference_loss"]),
        "token_logprob_rms_diff": float(np.sqrt((err ** 2).sum() / w.sum())),
        "token_logprob_max_diff": float(np.abs(err).max()),
        "program_grad_norm": pp ** 0.5, "reference_grad_norm": rr ** 0.5,
        "grad_norm_ratio": (pp / rr) ** 0.5,
        "grad_cosine": dot / (pp * rr) ** 0.5 if pp and rr else 0.0,
        "grad_rel_diff": (dd / rr) ** 0.5,
        # where a disagreement sits: norms by projection and LoRA factor
        "by_group": {k: {"program": v[0] ** 0.5, "reference": v[1] ** 0.5,
                         "rel_diff": (v[2] / v[1]) ** 0.5 if v[1] else None}
                     for k, v in groups.items()},
        "device": _device(), "reference_device": ref["device"],
    }
    out["ok"] = bool(
        out["loss_abs_diff"] <= tol["loss_abs"]
        and out["token_logprob_rms_diff"] <= tol["token_logprob_rms"]
        and abs(out["grad_norm_ratio"] - 1.0) <= tol["grad_norm_rel"]
        and out["grad_cosine"] >= tol["grad_cosine_min"])
    return out


def train_program(args) -> dict:
    """The program's side, computed in every run."""
    import numpy as np

    with open(args.spec) as f:
        spec = json.load(f)
    with open(args.reference) as f:
        ref = json.load(f)
    arrays = np.load(os.path.join(os.path.dirname(args.reference),
                                  ref["arrays"]))
    model, params, batch = train_inputs(_configuration(args.model_file),
                                        spec)
    p_loss, p_picked, p_grads = program_side(model, params, batch)
    return compare_train(p_loss, p_picked, p_grads, ref, arrays,
                         batch["loss_mask"], spec["tolerance"])


def check_serve(args) -> dict:
    import jax
    import jax.numpy as jnp

    with open(args.cases) as f:
        cases = json.load(f)
    _enable_cache()
    config = _configuration(args.model_file)
    reference = spec_lib.load_reference(config, "serve")
    model = _program_model(config, None)
    # Exactly what scripts/serve.py --random-init does, so the same weights.
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    sizes = reference.sizes(config)

    @jax.jit
    def logprobs(params, ids):
        return jax.nn.log_softmax(reference.forward(params, sizes, ids), -1)

    out = []
    for case in cases:
        prompt, chosen = case["prompt_ids"], case["tokens"]
        ids = jnp.asarray(prompt + chosen, jnp.int32)
        # Pad to a multiple of 64 so a few lengths share one compilation;
        # causal attention makes what follows a position invisible to it.
        pad = (-ids.shape[0]) % 64
        lp = logprobs(params, jnp.pad(ids, (0, pad)))
        at = jnp.arange(len(prompt) - 1, len(prompt) - 1 + len(chosen))
        rows = lp[at]
        out.append({
            "key": case["key"],
            "logprobs": [float(x) for x in
                         rows[jnp.arange(len(chosen)), jnp.asarray(chosen)]],
            "best_logprobs": [float(x) for x in rows.max(-1)],
        })
    return {"cases": out, "device": _device()}


WHAT = {"train-reference": train_reference, "train-program": train_program,
        "serve": check_serve}


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("what", choices=tuple(WHAT))
    p.add_argument("--model-file", required=True)
    p.add_argument("--spec")
    p.add_argument("--cases")
    p.add_argument("--reference")
    p.add_argument("--out", required=True)
    p.add_argument("--platform", default="",
                   help="refuse (exit 3) to compute on any other platform")
    args = p.parse_args()
    if args.platform:
        import jax

        found = jax.devices()[0].platform
        if found != args.platform:
            print(f"check.py: JAX reports platform {found!r}, not "
                  f"{args.platform!r}", file=sys.stderr)
            sys.exit(3)
    result = WHAT[args.what](args)
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f)
    os.replace(tmp, args.out)


if __name__ == "__main__":
    main()
