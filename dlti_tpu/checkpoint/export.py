"""Consolidated model export: sharded training state → portable artifact.

The capability chain the reference assembles from DeepSpeed + PEFT:
gather sharded weights on save (``stage3_gather_16bit_weights_on_model_save``,
``configs/ds_config_zero3.json:36``) then merge LoRA into the base model for
serving (vLLM leg, ``README.md:10``). Here: fold LoRA factors into base
kernels (:func:`~dlti_tpu.models.lora.merge_lora_params`), gather to host,
and write a single manifest-verified pytree artifact
(:func:`~dlti_tpu.checkpoint.store.save_pytree`) + config JSON that the
serving engine loads directly.
"""

from __future__ import annotations

import json
import os
from typing import Tuple

import jax

from dlti_tpu.checkpoint.store import load_pytree, save_pytree
from dlti_tpu.config import Config
from dlti_tpu.models.lora import merge_lora_params


def export_merged_model(directory: str, params, cfg: Config,
                        merge_lora: bool = True) -> str:
    """Write ``directory/model`` (manifest-verified pytree) +
    ``directory/config.json``.

    ``params`` may be sharded; leaves are gathered to host first (the
    16-bit-gather-on-save analog). Returns the export directory.
    """
    directory = os.path.abspath(directory)
    os.makedirs(directory, exist_ok=True)
    host_params = jax.device_get(params)
    if merge_lora and cfg.lora.enabled:
        host_params = merge_lora_params(host_params, alpha=cfg.lora.alpha)

    save_pytree(os.path.join(directory, "model"), host_params)

    meta = cfg.to_dict()
    meta["lora"]["enabled"] = False if merge_lora else meta["lora"]["enabled"]
    with open(os.path.join(directory, "config.json"), "w") as f:
        json.dump(meta, f, indent=2, sort_keys=True)
    return directory


def export_params_host(checkpoint_dir: str, step: int,
                       out_dir: str) -> str:
    """Host-side candidate export for the deployment controller: extract
    the ``.params`` subtree of a committed train-state checkpoint straight
    from its manifest — no model init, no optimizer state read, no device
    memory — and re-write it as a digest-verified :func:`save_pytree`
    artifact (the exact shape ``POST /v1/reload`` and ``request_reload``
    consume). Every leaf's SHA-256 is checked against the manifest while
    reading, so a corrupt checkpoint raises instead of exporting garbage.
    Returns the export's manifest SHA-256.
    """
    from dlti_tpu.checkpoint import store as _store

    root = os.path.join(os.path.abspath(checkpoint_dir), str(step))
    try:
        with open(os.path.join(root, "MANIFEST.json")) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        raise _store.CheckpointCorruptError(
            f"unreadable manifest for step {step} under {checkpoint_dir}: "
            f"{e}") from e
    prefix = ".params"
    params: dict = {}
    n = 0
    for entry in manifest.get("leaves", []):
        name = entry["name"]
        if not name.startswith(prefix + "["):
            continue
        keys = _store._KEY_RE.findall(name[len(prefix):])
        if not keys or prefix + "".join(
                f"['{k}']" for k in keys) != name:
            raise ValueError(
                f"checkpoint leaf {name!r} is not a dict-keyed params "
                "path; host-side export only handles nested-dict params")
        path = os.path.join(root, entry["file"])
        with open(path, "rb") as f:
            raw = f.read()
        if len(raw) != entry["size"] or \
                _store._sha256_bytes(raw) != entry["sha256"]:
            raise _store.CheckpointCorruptError(
                f"array file {entry['file']} for step {step} failed "
                "integrity check during export")
        node = params
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = _store._decode_leaf(raw, entry)
        n += 1
    if n == 0:
        raise ValueError(
            f"checkpoint step {step} under {checkpoint_dir} has no "
            ".params leaves — not a train-state checkpoint?")
    out_dir = os.path.abspath(out_dir)
    save_pytree(out_dir, params)
    digest = _store.manifest_digest(out_dir)
    if digest is None:
        raise _store.CheckpointCorruptError(
            f"export {out_dir} has no committed manifest digest")
    return digest


def load_exported_model(directory: str) -> Tuple[dict, Config]:
    """Load a consolidated export → (params, config). Used by serving."""
    directory = os.path.abspath(directory)
    with open(os.path.join(directory, "config.json")) as f:
        cfg = Config.from_dict(json.load(f))
    params = load_pytree(os.path.join(directory, "model"))
    return params, cfg
