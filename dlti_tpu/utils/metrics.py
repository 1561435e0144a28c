"""Metrics: reference CSV schema + TPU-native additions (tokens/sec/chip, MFU).

Reference schema (``training/train_baseline.py:246-255``, appended to
``results/training_metrics.csv`` by ``training/utils.py:51-69``):
``experiment, num_gpus, zero_stage, strategy, training_time_hours,
samples_per_second, peak_memory_gb, final_loss``.

We keep those columns byte-compatible (``num_gpus`` meaning "num chips") so
the reference's analysis workflow ports directly, and append
``tokens_per_second_per_chip`` and ``mfu_percent`` — the BASELINE.json north
star metrics.
"""

from __future__ import annotations

import csv
import dataclasses
import os
from dataclasses import dataclass
from typing import Optional

# The reference repo's CSV schema (``training/train_baseline.py:246-255``)
# — the byte-compatible column set MetricsRecord starts from. The parity
# contract: every metrics surface we add (the CSV extensions below, the
# telemetry per-step JSONL stream) must stay a SUPERSET of these columns
# so the reference's analysis workflow keeps porting directly (guarded by
# tests/test_telemetry.py).
REFERENCE_CSV_COLUMNS = (
    "experiment", "num_gpus", "zero_stage", "strategy",
    "training_time_hours", "samples_per_second", "peak_memory_gb",
    "final_loss",
)

# Peak dense bf16 FLOP/s of one chip, keyed by the exact ``device_kind``
# string JAX reports (the spellings jax 0.9.0 knows, both aliases of each).
# Source of every number: the Google Cloud TPU documentation page of that
# generation ("TPU v4", "TPU v5e", "TPU v5p", "TPU v6e" system
# architecture). "TPU v5 lite" is the string a v5e chip reported to this
# repo; the others have not been seen by it. A device that is not here is
# an error, never a default: an MFU over a made-up peak is worse than none.
CHIP_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


@dataclass
class MetricsRecord:
    experiment: str
    num_gpus: int  # column name kept for reference CSV parity; = num chips
    zero_stage: int
    strategy: str
    training_time_hours: float
    samples_per_second: float
    peak_memory_gb: float
    final_loss: float
    tokens_per_second_per_chip: float = 0.0
    # None where there is no chip to be a fraction of (CPU runs).
    mfu_percent: Optional[float] = None
    # Where peak_memory_gb came from: "device" (PJRT memory stats — real
    # HBM) or "host_rss" (process VmHWM fallback) — two different
    # quantities that must not be read as one (see device_peak_memory).
    peak_memory_source: str = "none"
    # Held-out eval loss at the last eval (nan when eval never ran).
    eval_loss: float = float("nan")

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def training_flops_per_token(num_params: int, trainable_params: Optional[int] = None) -> float:
    """Approximate FLOPs/token for one train step.

    Full fine-tune: ~6N (fwd 2N + bwd 4N). LoRA: bwd skips dW for frozen
    params (~2N of the 4N), giving ~4N + small adapter terms.
    """
    if trainable_params is not None and trainable_params < 0.5 * num_params:
        return 4.0 * num_params
    return 6.0 * num_params


def compute_mfu(
    tokens_per_second_per_chip: float,
    num_params: int,
    chip_peak_flops: Optional[float],
    trainable_params: Optional[int] = None,
) -> Optional[float]:
    """Model FLOPs Utilization in percent; None when there is no chip peak
    (``chip_peak_flops()`` on the CPU backend)."""
    if chip_peak_flops is None:
        return None
    achieved = tokens_per_second_per_chip * training_flops_per_token(
        num_params, trainable_params
    )
    return 100.0 * achieved / chip_peak_flops


def chip_peak_flops() -> Optional[float]:
    """Peak bf16 FLOP/s of one local chip, from :data:`CHIP_PEAK_FLOPS`.

    None on the CPU backend — a CPU run has no MFU. An accelerator whose
    ``device_kind`` is not in the table raises."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    try:
        return CHIP_PEAK_FLOPS[dev.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for device_kind "
            f"{dev.device_kind!r} (platform {dev.platform!r}); add it to "
            f"dlti_tpu.utils.metrics.CHIP_PEAK_FLOPS with its source"
        ) from None


def device_memory_stats() -> dict:
    """Per-device PJRT memory stats: ``{device_str: stats_dict}`` for every
    local device that reports them (the CPU backend returns None — those
    devices are simply absent). The raw map behind
    :func:`device_peak_memory` and the memory ledger's reconciliation
    (``dlti_tpu.telemetry.memledger``)."""
    import jax

    out = {}
    for dev in jax.local_devices():
        stats = dev.memory_stats()
        if stats:
            out[str(dev)] = dict(stats)
    return out


def device_peak_memory() -> tuple:
    """Peak memory as ``(gb, source)`` (the
    ``torch.cuda.max_memory_allocated`` analog, reference
    ``train_baseline.py:253``).

    Aggregates across ALL local devices — the per-process peak is the sum
    of each chip's ``peak_bytes_in_use`` (one process drives every chip of
    a host; reading only device 0 under-reports by the chip count).
    ``source`` is ``"device"`` (PJRT memory stats — real HBM) or, on the
    CPU backend only, ``"host_rss"`` (process VmHWM). Device HBM and host
    RSS are different quantities; consumers of the CSV must be able to
    tell them apart, hence the explicit source. An accelerator that
    reports no stats raises: a host number must never stand in for HBM.
    """
    import jax

    total = 0
    for stats in device_memory_stats().values():
        total += stats.get("peak_bytes_in_use",
                           stats.get("bytes_in_use", 0)) or 0
    if total:
        return total / 1024**3, "device"
    platform = jax.devices()[0].platform
    if platform != "cpu":
        raise RuntimeError(
            f"platform {platform!r} reported no device memory stats; "
            f"refusing to report host RSS in their place")
    with open("/proc/self/status") as f:  # peak resident set, linux procfs
        for line in f:
            if line.startswith("VmHWM:"):
                return int(line.split()[1]) / 1024**2, "host_rss"  # kB->GB
    return 0.0, "none"


def save_training_metrics(metrics: MetricsRecord | dict,
                          csv_path: str = "results/training_metrics.csv") -> None:
    """Append a row; write header on first write (``training/utils.py:51-69``).

    Schema-tolerant: when the existing file's header differs (a column was
    added since it was written), the file is rewritten under the union of
    columns instead of appending misaligned rows.
    """
    row = metrics.to_dict() if isinstance(metrics, MetricsRecord) else dict(metrics)
    os.makedirs(os.path.dirname(csv_path) or ".", exist_ok=True)
    old_fields: list = []
    if os.path.isfile(csv_path):
        with open(csv_path, newline="") as f:
            old_fields = next(csv.reader(f), []) or []
    if old_fields and set(old_fields) == set(row):
        # Same columns (possibly reordered keys in a dict row): plain
        # append in the file's own column order.
        with open(csv_path, "a", newline="") as f:
            csv.DictWriter(f, fieldnames=old_fields).writerow(row)
        return
    if old_fields and old_fields != list(row.keys()):
        # Header changed (a column was added since the file was written):
        # rewrite under the union of columns — via a temp file + atomic
        # replace, so a preemption mid-rewrite can never destroy history.
        with open(csv_path, newline="") as f:
            old_rows = list(csv.DictReader(f))
        fields = old_fields + [k for k in row if k not in old_fields]
        tmp_path = csv_path + ".tmp"
        with open(tmp_path, "w", newline="") as f:
            writer = csv.DictWriter(f, fieldnames=fields, restval="")
            writer.writeheader()
            for r in old_rows:
                writer.writerow(r)
            writer.writerow(row)
        os.replace(tmp_path, csv_path)
        return
    with open(csv_path, "a", newline="") as f:
        writer = csv.DictWriter(f, fieldnames=list(row.keys()))
        if not old_fields:
            writer.writeheader()
        writer.writerow(row)


def print_metrics_summary(metrics: MetricsRecord | dict) -> None:
    """Formatted stdout dump (``training/utils.py:72-88``)."""
    row = metrics.to_dict() if isinstance(metrics, MetricsRecord) else dict(metrics)
    print("\n" + "=" * 60)
    print("TRAINING METRICS SUMMARY")
    print("=" * 60)
    for k, v in row.items():
        if isinstance(v, float):
            print(f"  {k:<28} {v:.4f}")
        else:
            print(f"  {k:<28} {v}")
    print("=" * 60 + "\n")
