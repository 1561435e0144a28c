"""Per-layer metric ``prefix_hit_token_share``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "prefix_hit_token_share"
UNIT = "%"
BETTER = "higher"
LAYER = "prefix cache (serving/prefix_cache.py)"
MOVES = "output_tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    """Share of the window's prompt tokens that were met in the prefix
    cache: d ``prefix_cached_tokens`` over that plus d ``prefill_tokens``
    (what went through a prefill program) and d ``prefix_restored_tokens``.
    Every admitted prompt token is in exactly one of the three. None for a
    server without the counters or a window without admissions."""
    import stats

    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    cached = stats.counter_delta(a, b, "dlti_prefix_cached_tokens")
    filled = stats.counter_delta(a, b, "dlti_prefill_tokens")
    restored = stats.counter_delta(a, b, "dlti_prefix_restored_tokens")
    if cached is None or filled is None or not cached + filled:
        return None
    return 100.0 * cached / (cached + filled + (restored or 0.0))
