"""The process that holds the chip: the program's own entry point, started
from here so that the benchmark can name the model and watch the compiler.

    python benchmark/lib/chip_child.py --entry scripts/serve.py \
        --model-file run/model.json --model-name bench_qwen2_7b \
        --events run/compile_events.jsonl --facts run/device.json -- <argv>

Before the entry point runs this (1) registers the configuration's sizes as
a model the program can be asked for by name (``MODEL_PRESETS`` — so a new
configuration is a file, not an edit of the program), (2) records every
compilation and every compile-cache hit with its wall-clock time, so the
harness can prove that nothing compiled inside the measured window, and
(3) writes what JAX says the devices are. When the entry point returns it
adds each device's peak memory. Nothing else of the program is touched: the
entry point runs as ``__main__`` with the argv a user would give it.
"""

from __future__ import annotations

import argparse
import json
import os
import runpy
import sys
import time

# HF config.json key -> dlti_tpu.config.ModelConfig field.
HF_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "max_position_embeddings": "max_seq_len", "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps", "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "attention_bias",
}
_ACTIVATIONS = {"silu": "silu", "gelu_pytorch_tanh": "gelu_tanh",
                "gelu": "gelu_exact"}
_DTYPES = {"bfloat16": "bfloat16", "float32": "float32", "float16": "float16"}


def model_fields(config: dict) -> dict:
    """ModelConfig keyword arguments from a configuration file's ``model``
    object (the keys of the model's public config.json). ``program`` in the
    file, where present, gives further ModelConfig fields verbatim."""
    hf = config["model"]
    fields = {ours: hf[theirs] for theirs, ours in HF_KEYS.items()
              if theirs in hf}
    if hf.get("use_sliding_window", True) and hf.get("sliding_window"):
        fields["sliding_window"] = int(hf["sliding_window"])
    if "hidden_act" in hf:
        fields["mlp_activation"] = _ACTIVATIONS[hf["hidden_act"]]
    if "torch_dtype" in hf:
        fields["dtype"] = fields["param_dtype"] = _DTYPES[hf["torch_dtype"]]
    fields.update(config.get("program", {}))
    return fields


def _register_model(name: str, config: dict) -> None:
    from dlti_tpu.config import MODEL_PRESETS, ModelConfig

    MODEL_PRESETS[name] = ModelConfig(**model_fields(config))


def _watch_compiler(path: str) -> None:
    """One JSON line per compilation or compile-cache event, flushed."""
    import jax.monitoring as monitoring

    out = open(path, "a", buffering=1)

    def on_duration(event: str, seconds: float, **_kw) -> None:
        if "compile" in event or "cache" in event:
            out.write(json.dumps({"t": time.time(), "event": event,
                                  "seconds": seconds}) + "\n")

    def on_event(event: str, **_kw) -> None:
        if "compile" in event or "cache" in event:
            out.write(json.dumps({"t": time.time(), "event": event}) + "\n")

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def _device_facts() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def _memory_peaks() -> dict:
    import jax

    peaks = {}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks[str(d.id)] = stats.get("peak_bytes_in_use")
    return peaks


def _write_json(path: str, obj: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--entry", required=True)
    p.add_argument("--model-file", required=True)
    p.add_argument("--model-name", required=True)
    p.add_argument("--events", required=True)
    p.add_argument("--facts", required=True)
    p.add_argument("argv", nargs=argparse.REMAINDER)
    args = p.parse_args()
    root = os.getcwd()
    sys.path.insert(0, root)
    with open(args.model_file) as f:
        _register_model(args.model_name, json.load(f))
    _watch_compiler(args.events)
    facts = _device_facts()
    _write_json(args.facts, facts)
    sys.argv = [args.entry] + [a for a in args.argv if a != "--"]
    try:
        runpy.run_path(os.path.join(root, args.entry), run_name="__main__")
    finally:
        _write_json(args.facts, {**facts, "memory_peak_bytes": _memory_peaks()})


if __name__ == "__main__":
    main()
