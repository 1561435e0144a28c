"""A prefill program alone on the chip, tried without losing the call.

One configuration's ``jit(prefill)`` (seeded weights of its own, the
executor's program, two calls and then five timed ones, the compiler's
account of its memory beside them), one child process a variant, under a
parent that never touches JAX: it reads the child's lines, kills the
child's process group ``--wait`` seconds after ``compiled`` with no second
``DONE``, removes libtpu's lock file and goes on to the next variant
(``PERF.md`` section 7 item 6: which of nemotron3_nano_30b's programs never
return, and what that was bisected to; section 6, PR 47: what the head over
every position cost each configuration's calls).

    chiprun -- python3 benchmarks_dev/prefill_hang_drill.py \\
        grouped+f1792:2x1024 masked+f1792:2x2048 xing4_29b:1x2048

A variant is ``<words>:<rows>x<bucket>[:<layers>]``, the words joined by +:
the name of a file in ``benchmark/configs`` (default nemotron3_nano_30b;
its widths as published, the engine's shapes from the configuration's
serving cell, as many of its layers as the file runs unless ``<layers>``
says fewer); ``masked`` (every expert layer on the mask) or ``grouped``
(the default: every call of ``GROUPED_MIN_TOKENS`` or more through the
kernel, where it takes the width the experts are held at); ``f<N>`` the
routed experts published N wide (``f2048``: nemotron3_nano_30b's held at
whole chunks); ``unpadded`` the experts held as published (1,856: the
program of before PR 50, on the mask); ``nokernel`` the grouped layout
and gathers with
the kernel replaced by the identity on its rows; ``full`` no padding tokens
(default: bucket - 8 real tokens a row); ``ctx<N>`` every row starts at
position N over a context of N cached rows (default 0: fresh rows; the pool
is zeros, which times as any other content does); ``kb<N>`` the latent
family's prefill kernel with tiles of N queries and keys
(``models.latent.KERNEL_BLOCK``; a checkout without it ignores the word);
``trace``
three more calls under the profiler, then the seconds a call spends under
each ``dlti_`` scope and in each family of operations
(``benchmark/lib/scope_time.py``, ``reduce_trace.py``); ``tiny`` a test-width preset
(nemotron_h_tiny, or the one of ``MODEL_PRESETS`` a word names), for a try
on the CPU. Prints ``RETURNED`` or ``DID NOT RETURN`` a variant; exit 0
either way. ``--dump DIR`` saves each variant's returned logits there, to
lay beside another checkout's. The file imports the package it lies in: a
copy under another checkout's ``benchmarks_dev/`` drills that checkout's
program.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import queue
import signal
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T0 = time.time()


def say(*a):
    print("[%6.1f]" % (time.time() - T0), *a, flush=True)


def child(words: set, rows: int, bucket: int, layers: int,
          dump: str | None = None) -> None:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark", "lib")]
    import glob

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlti_tpu.config import MODEL_PRESETS, ModelConfig
    from dlti_tpu.models import build_model, latent, moe
    from dlti_tpu.ops.kv_cache import init_cache, window_blocks
    from dlti_tpu.ops.pallas import grouped_experts as kernel
    from dlti_tpu.serving.engine import EngineConfig
    from dlti_tpu.serving.executor import EngineExecutor

    say("imports", jax.devices()[0].device_kind)
    if "tiny" in words:
        cfg, blocks, model_len = MODEL_PRESETS[next(
            (w for w in sorted(words) if w in MODEL_PRESETS),
            "nemotron_h_tiny")], 256, 256
        moe.GROUPED_MIN_TOKENS, moe.GROUPED_TILE_ROWS = 16, 8
        kernel.WIDTH_CHUNK = 8
    else:
        from chip_child import model_fields

        configs = os.path.join(ROOT, "benchmark", "configs")
        name = next((w for w in sorted(words) if os.path.exists(
            os.path.join(configs, w + ".json"))), "nemotron3_nano_30b")
        with open(os.path.join(configs, name + ".json")) as fh:
            fields = model_fields(json.load(fh))
        with open(glob.glob(os.path.join(
                ROOT, "benchmark", "cells", "serve.%s.*.json" % name))[0]) as fh:
            cell = json.load(fh)["args"]
        blocks = int(cell["--num-blocks"])
        model_len = int(cell["--max-model-len"])
        for w in words:
            if w[0] == "f" and w[1:].isdigit():
                fields["moe_intermediate_size"] = int(w[1:])
        if layers:
            if fields.get("layer_pattern"):
                fields["layer_pattern"] = fields["layer_pattern"][:layers]
                layers = len(fields["layer_pattern"])
            if fields.get("layer_windows"):
                fields["layer_windows"] = fields["layer_windows"][:layers]
            fields["num_layers"] = layers
        cfg = dataclasses.replace(
            ModelConfig(**fields), paged_attention_impl="kernel")
        say("configuration", name, "layers", cfg.num_layers, "blocks", blocks,
            "max_model_len", model_len)
    if "unpadded" in words:
        kernel.held_width = lambda width: width
    for w in words:
        if w[:2] == "kb" and w[2:].isdigit() \
                and hasattr(latent, "KERNEL_BLOCK"):
            latent.KERNEL_BLOCK = int(w[2:])
    start = next((int(w[3:]) for w in words
                  if w[:3] == "ctx" and w[3:].isdigit()), 0)
    if "masked" in words:
        moe.takes_grouped = lambda tokens, width: False
    if "nokernel" in words:
        kernel.grouped_experts = lambda x, *rest, **kw: x

    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def leaf(key, shape, dtype, ones):
        if ones:
            return jnp.ones(shape, dtype)
        scale = shape[-2] ** -0.5 if len(shape) > 1 else 0.02
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)

    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.PRNGKey(1), len(flat))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf(k, v.shape, v.dtype, v.ndim == 1 and any(
            w in jax.tree_util.keystr(p).lower() for w in ("norm", "scale")))
        for k, (p, v) in zip(keys, flat)])
    jax.block_until_ready(params)
    say("params GB", round(sum(v.size * v.dtype.itemsize for v in
                               jax.tree_util.tree_leaves(params)) / 1e9, 2))
    call_tokens = getattr(model, "prefill_call_tokens", 0)
    cache = init_cache(cfg, blocks, 16, 32, jnp.bfloat16,
                       call_tokens=call_tokens)
    # The executor's prefill program without an engine round it.
    ex = EngineExecutor.__new__(EngineExecutor)
    ex.model = model
    ex.counter_names = tuple(getattr(model, "counter_names", ()))
    ex._recurrent, ex.adapter_pool = cfg.has_recurrent_state, None
    ex._row_extra = "state_slots" if ex._recurrent else None
    ex.kv_groups = cfg.kv_group_windows
    ex._layer_groups = None if len(ex.kv_groups) == 1 else [
        cfg.kv_group_of_layer(i) for i in range(cfg.num_layers)]
    ex.cfg = EngineConfig(max_seqs=32, block_size=16, num_blocks=blocks,
                          max_model_len=model_len)
    real = bucket if "full" in words else bucket - 8
    ids = np.random.default_rng(7).integers(
        1, cfg.vocab_size - 1, (rows, bucket)).astype(np.int32)
    at = np.arange(bucket, dtype=np.int32)[None, :]
    positions = np.where(at < real, start + at, -1) \
        * np.ones((rows, 1), np.int32)
    # The table as the engine forms it: a row's whole table where the model
    # asks for that, else the call's own blocks; a window group its own.
    used = (start + bucket) // 16
    width = model_len // 16 \
        if getattr(model, "prefill_whole_tables", False) else used
    tables = np.zeros((rows, width), np.int32)
    tables[:, :used] = 1 + np.arange(rows * used).reshape(rows, used)
    if ex._layer_groups is not None:
        wide = window_blocks(ex.kv_groups[1], 16, call_tokens)
        windows = np.zeros((rows, wide), np.int32)
        windows[:, :used] = tables[:, :used]
        tables = ({"block_tables": tables},
                  {"block_tables": windows,
                   "table_base": np.zeros((rows,), np.int32)})
    args = (jnp.asarray(ids), jnp.asarray(positions),
            jax.tree_util.tree_map(jnp.asarray, tables),
            jnp.full((rows,), real - 1, jnp.int32),
            *((jnp.arange(rows, dtype=jnp.int32),) if ex._recurrent else ()))
    lowered = ex._build_prefill_fn(bucket).lower(params, cache, *args)
    say("lowered")
    program = lowered.compile()
    say("compiled")
    memory = program.memory_analysis()
    say("MEMORY", json.dumps({k: getattr(memory, k + "_size_in_bytes", None)
                              for k in ("temp", "argument", "output", "alias",
                                        "generated_code")}))
    times = []
    for i in range(7):
        t = time.time()
        out = program(params, cache, *args)
        if i < 2:
            say("dispatched", i)
        cache, last, *counters = out
        jax.block_until_ready(out)
        times.append(round(1e3 * (time.time() - t), 3))
        if i < 2:
            say("DONE", i, "%.3f s" % (time.time() - t), "counters",
                np.asarray(counters).tolist())
    stats = jax.devices()[0].memory_stats() or {}
    say("TIMES", json.dumps({
        "variant": "+".join(sorted(words)), "shape": [rows, bucket],
        "ms": times[2:], "median_ms": sorted(times[2:])[2],
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "logits": list(last.shape)}))
    if "trace" in words:
        import tempfile

        traced = tempfile.mkdtemp(prefix="drill_trace_")
        with jax.profiler.trace(traced):
            for _ in range(3):
                cache, *rest = program(params, cache, *args)
                jax.block_until_ready(rest)
        lib = os.path.join(ROOT, "benchmark", "lib")
        for script, label in (("scope_time.py", "SCOPES"),
                              ("reduce_trace.py", "OPS")):
            out = os.path.join(traced, label + ".json")
            subprocess.run(
                [sys.executable, os.path.join(lib, script), traced, "--out",
                 out], env={**os.environ, "JAX_PLATFORMS": "cpu"},
                capture_output=True, timeout=600)
            with open(out) as fh:
                got = json.load(fh) or {}
            say(label, json.dumps(
                got.get("programs", {}).get("prefill") if label == "SCOPES"
                else {"prefill": got.get("programs", {}).get("prefill"),
                      "device_ops": got.get("device_ops")}))
    if dump:  # the last rows' logits, to lay beside another checkout's
        os.makedirs(dump, exist_ok=True)
        np.save(os.path.join(dump, "%s_%dx%d.npy" % (
            "+".join(sorted(words)), rows, bucket)), np.asarray(last))


def run_child(variant: str, wait: int, overall: int,
              dump: str | None = None) -> bool:
    say("=== child", variant)
    p = subprocess.Popen(
        [sys.executable, "-u", os.path.abspath(__file__), "--child", variant]
        + (["--dump", dump] if dump else []),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        start_new_session=True)
    lines: queue.Queue = queue.Queue()
    threading.Thread(target=lambda: [lines.put(x) for x in p.stdout]
                     + [lines.put(None)], daemon=True).start()
    start, compiled, done, tail = time.time(), None, 0, []
    while True:
        try:
            line = lines.get(timeout=1)
        except queue.Empty:
            line = ""
        if line is None:
            break
        if line:
            tail.append(line.rstrip())
            if line.startswith("["):
                print("   ", line.rstrip(), flush=True)
            compiled = compiled or ("compiled" in line and time.time())
            done += "DONE" in line
        now = time.time()
        if done < 2 and ((compiled and now - compiled > wait)
                         or now - start > overall):
            say("KILLING: no return %.0f s after the compile (%.0f s in all)"
                % (now - (compiled or start), now - start))
            os.killpg(p.pid, signal.SIGKILL)
            break
    p.wait()
    time.sleep(3)
    if done < 2:
        print("    ... " + "\n    ... ".join(t[:300] for t in tail[-6:]),
              flush=True)
        try:  # a killed child's lock would refuse the next one the chip
            os.remove("/tmp/libtpu_lockfile")
        except OSError:
            pass
    say("=== result", variant,
        "RETURNED" if done >= 2 else "DID NOT RETURN", "rc", p.returncode)
    return done >= 2


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("variants", nargs="*", default=["grouped+f1792:2x1024"])
    ap.add_argument("--child", default=None, help="run this one variant here")
    ap.add_argument("--wait", type=int, default=60,
                    help="seconds after the compile before a child is killed")
    ap.add_argument("--overall", type=int, default=300)
    ap.add_argument("--dump", default=None,
                    help="a directory for each variant's returned logits")
    args = ap.parse_args()
    if args.child:
        words, shape, *layers = args.child.split(":")
        rows, bucket = map(int, shape.split("x"))
        child(set(words.split("+")), rows, bucket,
              int(layers[0]) if layers else 0, args.dump)
        return 0
    say("SUMMARY", json.dumps({
        v: run_child(v, args.wait, args.overall, args.dump)
        for v in args.variants}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
