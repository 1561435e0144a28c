"""Process-level JAX set-up shared by every entry point: where the
persistent compilation cache lives, CPU device meshes for tests and dry
runs, and the one-process-per-chip rule.

``JAX_PLATFORMS`` is a plain environment variable: JAX reads it at import
and nothing here re-asserts it. Importing this module, and the cache and
host-platform helpers, leave the backend uninitialised; :func:`device_facts`
and :func:`refuse_multiprocess_on_tpu` initialise it, because their job is
to ask it.
"""

from __future__ import annotations

import os
from typing import Optional

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compilation_cache_dir() -> Optional[str]:
    """Directory this code points the persistent compilation cache at, or
    None when ``JAX_COMPILATION_CACHE_DIR`` is set — JAX reads that variable
    itself and the code must set no other directory.

    The fallback is ``<checkout>/.jax_cache``, derived from the package's
    location only: the directory is part of how a cache is found again, so
    it may not depend on the working directory, a pid or the clock.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return os.path.join(_CHECKOUT, ".jax_cache")


def enable_compilation_cache(min_compile_secs: float = 0.0) -> None:
    """Turn on the persistent compilation cache. Every entry point calls
    this once before its first compile; processes of one run then share
    compiled programs (a trainer, the server it hands off to, a second
    smoke run).

    ``min_compile_secs`` is the compile time below which a program gets no
    entry. The default keeps everything: on the v5e the eager ops of a
    model init compile in about a second each, so under JAX's own 1 s
    floor which of them got an entry changed from run to run (a second
    identical run added three), and a warm start still recompiled them
    all. The test suite raises it — hundreds of tiny CPU compiles are
    cheaper to redo than to look up.
    """
    import jax

    cache_dir = compilation_cache_dir()
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)


def host_platform_env(n_devices: int, env: dict) -> dict:
    """Set the CPU-backend-with-``n_devices``-virtual-devices variables on
    ``env`` (this process's ``os.environ`` or a child's env dict)."""
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        )
    env["JAX_PLATFORMS"] = "cpu"
    return env


def force_host_platform(n_devices: int) -> None:
    """Force the CPU backend with ``n_devices`` virtual devices in this
    process, for mesh simulation (tests, dry runs).

    Must run before the backend initializes; afterwards it silently has no
    effect and the caller's device-count check reports the failure. JAX
    has already read ``JAX_PLATFORMS`` by the time it is imported, hence
    the config update next to the environment variable.
    """
    host_platform_env(n_devices, os.environ)
    import jax

    jax.config.update("jax_platforms", "cpu")


def device_facts() -> dict:
    """The devices as JAX reports them — the triple every result and
    build-time log line names. Initialises the backend."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def refuse_multiprocess_on_tpu(entry_point: str) -> None:
    """Exit with one line when ``entry_point`` is about to start several
    JAX processes on a host whose platform is TPU.

    A chip belongs to one process, and none of the local spawners
    (``--fleet-workers``, ``scripts/launch.py --num-processes N``,
    ``--elastic``) gives a child a chip of its own: every child would ask
    for the whole host and fail or hang. One process drives all chips of a
    host through the mesh instead. Initialises the backend to learn the
    platform; on TPU the caller exits right away (releasing the chips), on
    CPU holding the backend is harmless.
    """
    import jax

    if jax.default_backend() == "tpu":
        raise SystemExit(
            f"{entry_point}: several chip-holding processes on one host "
            f"are not supported on TPU (a chip belongs to one process; "
            f"found {jax.device_count()} x {jax.devices()[0].device_kind}). "
            f"One process drives every chip of the host: use "
            f"scripts/serve.py --replicas N / --tensor N, or "
            f"scripts/train.py --num-devices N / --tensor N.")
