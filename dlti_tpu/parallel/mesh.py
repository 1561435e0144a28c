"""Device mesh construction.

Axes (SURVEY.md §2c build targets):

* ``data``     — replicated data parallelism + ZeRO-1/2 optimizer sharding
                 (reference: torchrun DP, ``train_deepspeed_zero1.py:10-12``)
* ``fsdp``     — parameter sharding, the ZeRO-3 equivalent
                 (reference: ``configs/ds_config_zero3.json:17``)
* ``tensor``   — tensor parallelism over ICI (reference claims TP only for
                 the vLLM leg, ``README.md:10``)
* ``sequence`` — context/sequence parallelism (ring attention) for
                 long-context training; the reference truncates to 512 and
                 has no SP (SURVEY.md §5.7) — first-class here.

On real pods ``mesh_utils.create_device_mesh`` lays axes out so that the
innermost (most communication-heavy) axes ride ICI. On CPU (tests) we fall
back to a plain reshape of ``jax.devices()``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from dlti_tpu.config import ParallelConfig

MESH_AXES = ("data", "fsdp", "tensor", "sequence", "pipe", "expert")
# The axes batch rows shard over (ZeRO-3 shards rows over 'fsdp' as well).
BATCH_AXES = ("data", "fsdp")


def in_manual_region() -> bool:
    """True while tracing inside a ``shard_map`` (e.g. a pipeline stage).

    No try/except around the introspection: if a jax upgrade changes it,
    fail loud — silently answering "not nested" would route callers into a
    nested manual region (wrong gradients on this jax)."""
    am = jax.sharding.get_abstract_mesh()
    return (am is not None and not am.empty
            and any(ty == jax.sharding.AxisType.Manual and am.shape[name] > 1
                    for name, ty in zip(am.axis_names, am.axis_types)))


def build_mesh(cfg: ParallelConfig, devices: Optional[Sequence] = None) -> Mesh:
    """Build a 6-axis mesh (data, fsdp, tensor, sequence, pipe, expert)."""
    if devices is None:
        devices = jax.devices()
    shape = (cfg.data, cfg.fsdp, cfg.tensor, cfg.sequence, cfg.pipe,
             cfg.expert)
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(
            f"mesh shape {shape} needs {n} devices, have {len(devices)}"
        )
    if n < len(devices):
        # Single-process only: use the first n visible devices — the
        # `deepspeed --num_gpus=N` analog of an N-wide job on a larger host
        # (train.ipynb cells 5-33). Multi-process meshes must span every
        # process's local devices, so there the exact count is required.
        if jax.process_count() > 1:
            raise ValueError(
                f"mesh shape {shape} needs {n} devices but {len(devices)} are "
                f"visible across {jax.process_count()} processes; a "
                f"multi-process mesh must use all devices"
            )
        devices = list(devices)[:n]
    if devices[0].platform == "tpu":
        dev_array = mesh_utils.create_device_mesh(shape, devices=list(devices))
    else:
        dev_array = np.array(list(devices)).reshape(shape)
    return Mesh(dev_array, MESH_AXES)


def fit_parallel_to_devices(cfg: ParallelConfig,
                            n_devices: int) -> ParallelConfig:
    """Shrink the batch axes (``data``/``fsdp``) of a mesh config to fit
    ``n_devices`` — the mesh half of elastic reshape-on-failure: when a
    worker dies and the surviving world re-rendezvouses smaller, the
    model-parallel axes (tensor/sequence/pipe/expert) must keep their
    extent (the sharded program depends on them) while the batch extent
    absorbs the loss. No-op when the config already fits."""
    import dataclasses

    if cfg.num_devices <= n_devices:
        return cfg
    fixed = cfg.tensor * cfg.sequence * cfg.pipe * cfg.expert
    rows = n_devices // fixed
    if rows < 1:
        raise ValueError(
            f"cannot reshape mesh to {n_devices} devices: the "
            f"model-parallel extent tensor*sequence*pipe*expert={fixed} "
            "alone exceeds the surviving world")
    if cfg.data > 1 and cfg.fsdp > 1:
        raise ValueError(
            f"cannot reshape a mixed data={cfg.data} x fsdp={cfg.fsdp} "
            "mesh automatically; relaunch with explicit extents")
    if cfg.fsdp > 1:
        return dataclasses.replace(cfg, fsdp=rows)
    return dataclasses.replace(cfg, data=rows)


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host rendezvous — replaces the reference's launcher-set
    MASTER_ADDR/LOCAL_RANK env contract (``train_deepspeed_zero1.py:120-121``,
    ``train.ipynb:640-647``). With no args, JAX auto-detects cluster env
    (GKE/GCE metadata, SLURM, or MEGASCALE vars)."""
    if jax.config.jax_platforms == "cpu":
        # Multi-process CPU (the gloo test/dev path): the CPU client
        # builds with NO cross-process collectives by default, and every
        # multi-process computation then fails with "Multiprocess
        # computations aren't implemented on the CPU backend". Select the
        # gloo TCP implementation before the backend initializes.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
