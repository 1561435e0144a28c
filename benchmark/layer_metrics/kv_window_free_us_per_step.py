"""Per-layer metric ``kv_window_free_us_per_step``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "kv_window_free_us_per_step"
UNIT = "us/step"
BETTER = "lower"
LAYER = "cache allocator (serving/block_manager.py)"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    """Microseconds of the stepper's host path a decode step that the
    release of the window group's blocks took: d
    ``dlti_kv_window_free_seconds_total`` / d ``decode_steps`` over the
    window (the release runs inside ``engine/decode_plan`` and
    ``engine/prefill_launch``, whose phases hold it). None without the
    counter (the parent's program)."""
    import stats

    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    seconds = stats.counter_delta(a, b, "dlti_kv_window_free_seconds")
    steps = stats.counter_delta(a, b, "dlti_decode_steps")
    if seconds is None or not steps:
        return None
    return 1e6 * seconds / steps
