"""Rank-0 logging + step timing.

Reference analogs: main-process gating via ``is_main_process``
(``train_deepspeed_zero1.py:123,126``) / ``local_rank <= 0``
(``train_deepspeed_zero3.py:128``); per-10-step logging
(``logging_steps=10``, ``train_baseline.py:184``).
"""

from __future__ import annotations

import logging
import sys
import time
from contextlib import contextmanager


def is_main_process() -> bool:
    """True on the process that logs and writes run-level records.

    A process that never joined ``jax.distributed`` is alone and is the
    main one by definition — answering that must not initialise a backend
    (importing a package creates loggers, and a process that merely
    imports must not take the chip). Only a joined process asks JAX for
    its index, and it holds a backend anyway.
    """
    import jax

    if not jax.distributed.is_initialized():
        return True
    return jax.process_index() == 0


class _MainProcessFilter(logging.Filter):
    """INFO and below from the main process only; warnings from all.

    Evaluated per record, not at logger creation: module-scope loggers are
    created at import, before the launcher env has been turned into a
    ``jax.distributed`` membership."""

    def filter(self, record: logging.LogRecord) -> bool:
        return record.levelno >= logging.WARNING or is_main_process()


def get_logger(name: str = "dlti_tpu") -> logging.Logger:
    logger = logging.getLogger(name)
    if not logger.handlers:
        handler = logging.StreamHandler(sys.stdout)
        handler.setFormatter(
            logging.Formatter("%(asctime)s %(levelname)s %(name)s: %(message)s")
        )
        handler.addFilter(_MainProcessFilter())
        logger.addHandler(handler)
        logger.setLevel(logging.INFO)
        logger.propagate = False
    return logger


class StepTimer:
    """Wall-clock per-step timing with warm-up discard — the in-tree
    equivalent of DeepSpeed's ``wall_clock_breakdown`` (always available,
    reference keeps it disabled — ``configs/ds_config_zero1.json:48``)."""

    def __init__(self, warmup_steps: int = 2):
        self.warmup_steps = warmup_steps
        self._times: list = []
        self._t0: float | None = None
        self._count = 0
        # Most recent measured per-step time, warm-up included (the
        # per-step telemetry stream wants every step's own time, not the
        # smoothed mean the throughput summary uses).
        self.last_step_seconds = 0.0

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self, steps: int = 1) -> None:
        """``steps`` > 1: the timed span covered that many train steps
        (a steps_per_sync window); record the per-step time. Warm-up is
        counted in *steps*, so a scanned window past the warm-up budget
        still records (else steps_per_sync=K with max_steps=2K would
        discard every window and report 0 tok/s)."""
        dt = time.perf_counter() - self._t0
        steps = max(steps, 1)
        warm = self._count < self.warmup_steps
        self._count += steps
        self.last_step_seconds = dt / steps
        if not warm:
            self._times.append(dt / steps)

    @contextmanager
    def measure(self, steps: int = 1):
        self.start()
        yield
        self.stop(steps)

    @property
    def mean_step_seconds(self) -> float:
        return sum(self._times) / len(self._times) if self._times else 0.0

    @property
    def steps_per_second(self) -> float:
        m = self.mean_step_seconds
        return 1.0 / m if m > 0 else 0.0


@contextmanager
def profile_trace(log_dir: str, enabled: bool = True):
    """Capture a ``jax.profiler`` trace (view in TensorBoard/XProf) —
    the tracing capability the reference lacks (SURVEY.md §5.1)."""
    import jax

    if not enabled:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
