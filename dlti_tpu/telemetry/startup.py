"""Start-up counted from inside the program: how long after the process
began each start-up phase was passed, and what compiling or fetching
programs has cost so far.

``setup_s`` of a benchmark run (process start to the first measured request)
is taken from outside; these series say which part of it the program
itself spent where, so an odd reading can be put down to a phase:

* ``dlti_startup_<phase>_seconds`` gauges, written once each by
  :func:`mark_startup` as the entry point passes the phase (``imports``,
  ``weights``, ``kv_pool``, ``ready`` for the server): seconds since the
  kernel started this process.
* ``dlti_compilations_total`` / ``dlti_compile_seconds_total``: programs
  XLA compiled, and the seconds that took; ``dlti_compile_cache_hits_total``
  / ``dlti_compile_cache_fetch_seconds_total``: programs fetched from the
  persistent compilation cache instead. One ``jax.monitoring`` listener
  (:func:`install_compile_listener`) counts them; JAX calls it per
  compilation or fetch, never per step. A count that grows while a server
  is under load is a shape that start-up did not warm: once the ``ready``
  phase has been marked, every program compiled or fetched leaves one INFO
  line with its name (``compiled after ready: <fun_name>, <seconds> s,
  compiled | fetched; stepper in <phase>``), and the newest few are kept
  (:func:`recent_programs`) for the stepper's stall record.

Module-level like the watchdog and flight-recorder counters: the server's
registry and the trainer's sampler both read these objects.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Callable, Optional

from dlti_tpu.telemetry.registry import Counter, Gauge
from dlti_tpu.utils.logging import get_logger

STARTUP_PHASES = ("imports", "weights", "kv_pool", "ready")

startup_gauges = {
    phase: Gauge(f"dlti_startup_{phase}_seconds",
                 f"seconds from process start to the end of start-up phase "
                 f"'{phase}'")
    for phase in STARTUP_PHASES}
compilations_total = Counter(
    "dlti_compilations_total", "programs compiled by XLA (cache misses)")
compile_seconds_total = Counter(
    "dlti_compile_seconds_total", "seconds spent in those compilations")
compile_cache_hits_total = Counter(
    "dlti_compile_cache_hits_total",
    "programs fetched from the persistent compilation cache")
compile_cache_fetch_seconds_total = Counter(
    "dlti_compile_cache_fetch_seconds_total", "seconds spent in those fetches")

STARTUP_METRICS = (*startup_gauges.values(), compilations_total,
                   compile_seconds_total, compile_cache_hits_total,
                   compile_cache_fetch_seconds_total)

_IMPORTED_AT = time.monotonic()
_FETCH_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def process_age_s() -> float:
    """Seconds since the kernel started this process (``/proc/self/stat``
    field 22 against the boot clock); where that cannot be read, since this
    module was imported."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return time.monotonic() - _IMPORTED_AT


_ready = False


def mark_startup(phase: str) -> None:
    """The entry point has passed ``phase``: set its gauge to the process's
    age now. From ``ready`` on a program compiled or fetched is a late one
    and is logged by name."""
    global _ready
    startup_gauges[phase].set(process_age_s())
    if phase == "ready":
        _ready = True


_fetched = threading.local()
# The newest programs compiled or fetched: (name, seconds, "compiled" |
# "fetched"). Written by the listener on whichever thread compiled.
_recent: collections.deque = collections.deque(maxlen=8)
# ``() -> name`` of the serving stepper's open phase, for the late program's
# line (the stepper's account sets it when its thread is bound).
stepper_phase: Optional[Callable[[], str]] = None


def recent_programs(n: int) -> list:
    """The newest ``n`` (at most 8) programs compiled or fetched, oldest
    first, as ``(name, seconds, "compiled" | "fetched")``."""
    return list(_recent)[-n:] if n > 0 else []


def _on_duration(event: str, seconds: float, fun_name: str = "?",
                 **_kw) -> None:
    # JAX reports the whole of compile-or-fetch as a compile duration, and a
    # fetch inside it first: the flag keeps a fetch from counting twice. The
    # program's name comes with the compile duration alone.
    if event == _FETCH_EVENT:
        compile_cache_hits_total.inc()
        compile_cache_fetch_seconds_total.inc(seconds)
        _fetched.pending = True
    elif event == _COMPILE_EVENT:
        how = "compiled"
        if getattr(_fetched, "pending", False):
            _fetched.pending = False
            how = "fetched"
        else:
            compilations_total.inc()
            compile_seconds_total.inc(seconds)
        _recent.append((fun_name, seconds, how))
        if _ready:
            get_logger().info(
                "compiled after ready: %s, %.3f s, %s; stepper in %s",
                fun_name, seconds, how,
                stepper_phase() if stepper_phase is not None else "-")


_installed = False


def install_compile_listener() -> None:
    """Register the listener with ``jax.monitoring`` (once a process)."""
    global _installed
    if _installed:
        return
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _installed = True


def compile_scalars() -> dict:
    """The four compile series as plain numbers (the trainer's sampler)."""
    return {"compilations": compilations_total.value,
            "compile_seconds": compile_seconds_total.value,
            "compile_cache_hits": compile_cache_hits_total.value,
            "compile_cache_fetch_seconds":
                compile_cache_fetch_seconds_total.value}
