"""Per-step JSONL telemetry stream (rank-0) for training runs.

MegaScale attributes large-scale training goodput recovery chiefly to
in-framework per-step instrumentation; this is the stream that makes that
possible here. Three record types, one JSON object per line:

* ``{"type": "run", ...}``   — run-level metadata, written once at start
  (experiment name, chip count, strategy — the identifying half of the
  reference CSV schema).
* ``{"type": "step", ...}``  — one per optimizer step: step, loss,
  grad_norm, lr, tokens/s/chip, MFU, HBM peak (+ its source) and the
  measured step wall time.
* ``{"type": "final", ...}`` — the full :class:`MetricsRecord` dict at run
  end, which makes the stream a strict superset of the reference CSV
  columns by construction (guarded by ``tests/test_telemetry.py``).

Lines are flushed per write so a preempted run's stream is readable up to
the last completed step. Writes go through the durable writer's
drop-and-count stream (``path_class="steplog"``): an EIO/ENOSPC on the
telemetry disk costs log lines (counted in ``dlti_disk_write_errors_total``
and the writer's ``dropped``), never a training step.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Optional

from dlti_tpu.config import OptimizerConfig
from dlti_tpu.utils import durable_io
from dlti_tpu.utils.metrics import MetricsRecord

# Keys every "step" record carries (the per-step contract; the schema test
# asserts run ∪ step ∪ final covers the reference CSV columns). The
# sentinel fields (PR 8): `anomaly` is "" for a clean step or the verdict
# kind (nonfinite | loss_spike | grad_spike), `skipped_update` marks
# optimizer updates the in-step nonfinite gate skipped, and
# `rollbacks_total` is the run's cumulative automatic-rollback count —
# the triple an incident reader greps first. The goodput-ledger fields
# (PR 9, telemetry.ledger): per-phase wall clock accrued around this
# step — data/prefetch stall, device sync, checkpoint save+restore, and
# rollback+replay — divided evenly across a steps_per_sync window's
# records (checkpoint time issued after a record books to the next one).
# All 0.0 when the ledger is disabled. The memory-ledger fields (PR 11,
# telemetry.memledger): device bytes in use and the remaining headroom at
# this step's bookkeeping boundary — the per-step twins of the goodput
# phase fields, on the bytes axis. hbm_headroom_bytes is -1 when
# capacity is unknown (CPU runs without a configured budget); both are 0
# when the memory ledger is disabled. A model that counts in a training
# pass (``train_counters``: models.jamba's ``recurrent_state_resets``) adds
# its counters to its own rows under their names; other models' rows are
# these fields alone.
STEP_RECORD_FIELDS = (
    "type", "step", "loss", "grad_norm", "lr",
    "tokens_per_second_per_chip", "mfu_percent",
    "peak_memory_gb", "peak_memory_source", "step_time_s",
    "anomaly", "skipped_update", "rollbacks_total",
    "data_wait_s", "sync_s", "ckpt_s", "rollback_s",
    "hbm_bytes_in_use", "hbm_headroom_bytes",
)

RUN_RECORD_FIELDS = ("type", "experiment", "num_gpus", "zero_stage",
                     "strategy")


def metrics_csv_columns() -> tuple:
    """The reference-parity CSV schema (``utils.metrics.MetricsRecord``)."""
    return tuple(f.name for f in dataclasses.fields(MetricsRecord))


def jsonl_stream_columns() -> frozenset:
    """Union of keys the writer can emit across record types."""
    return frozenset(STEP_RECORD_FIELDS) | frozenset(RUN_RECORD_FIELDS) \
        | frozenset(metrics_csv_columns())


def schedule_lr(cfg: OptimizerConfig, step: int) -> float:
    """Host-side mirror of ``training.optimizer.build_schedule`` — the lr
    at ``step`` without a device round trip per logged step."""
    lr, w = cfg.learning_rate, max(cfg.warmup_steps, 1)
    if cfg.schedule == "warmup_constant":
        if cfg.warmup_steps <= 0:
            return lr
        return lr * min(1.0, step / w)
    if cfg.schedule == "warmup_cosine":
        total = max(cfg.total_steps, cfg.warmup_steps + 1)
        if step < w:
            return lr * step / w
        frac = min(1.0, (step - w) / max(1, total - w))
        return lr * 0.5 * (1.0 + math.cos(math.pi * frac))
    raise ValueError(f"unknown schedule {cfg.schedule!r}")


class StepLogWriter:
    """Append-mode JSONL writer; one instance per (rank-0) training run.

    Telemetry criticality: a failed write is dropped and counted, never
    raised — the step loop must survive a sick telemetry disk."""

    def __init__(self, path: str, run_meta: Optional[dict] = None):
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._w = durable_io.LineWriter(path, path_class="steplog")
        if run_meta is not None:
            self._write({"type": "run", **run_meta})

    def _write(self, obj: dict) -> None:
        self._w.write_line(json.dumps(obj))

    @property
    def dropped(self) -> int:
        """Lines lost to I/O errors (drop-and-count contract)."""
        return self._w.dropped

    def log_step(self, step: int, **fields) -> None:
        self._write({"type": "step", "step": step, **fields})

    def log_final(self, record: "MetricsRecord | dict") -> None:
        row = record.to_dict() if isinstance(record, MetricsRecord) \
            else dict(record)
        self._write({"type": "final", **row})

    def close(self) -> None:
        self._w.close()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False
