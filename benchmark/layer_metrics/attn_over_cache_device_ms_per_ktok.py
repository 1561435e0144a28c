"""Per-layer metric ``attn_over_cache_device_ms_per_ktok``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "attn_over_cache_device_ms_per_ktok"
UNIT = "ms/ktok"
BETTER = "lower"
LAYER = "attention over the cache (ops/attention.py)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """Device time a prefill call spends walking the paged cache, per
    thousand tokens of the context its queries reach: the operations of
    ``jit_prefill`` that the trace puts under the scope
    ``dlti_attn_over_cache`` (``scope_time``: the union of their intervals,
    a call in the mean), over the mean context of a prefill call in the
    window, its own tokens and the cached ones before them ((d
    ``prefill_tokens`` + d ``prefill_context_tokens``) / d
    ``prefill_batches``). A fusion counts under the scope of the operation
    the compiler named it after. None where the trace's operations carry
    no such scope (the parent's program), or without the counters."""
    import scope_time
    import stats

    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    tokens = stats.counter_delta(a, b, "dlti_prefill_tokens")
    before = stats.counter_delta(a, b, "dlti_prefill_context_tokens")
    calls = stats.counter_delta(a, b, "dlti_prefill_batches")
    if not tokens or before is None or not calls:
        return None
    per_call_s = scope_time.scope_s_per_call(
        ctx, "prefill", "dlti_attn_over_cache")
    if per_call_s is None:
        return None
    return 1e3 * per_call_s / ((tokens + before) / calls / 1e3)
