"""Per-layer metric ``prefill_device_ms_per_ktok.closed``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "prefill_device_ms_per_ktok.closed"
UNIT = "ms/ktok"
BETTER = "lower"
LAYER = "executor, prefill programs (serving/engine.py EngineExecutor)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """Mean device time of a prefill program execution (trace) over the
    mean prompt tokens of a prefill call in the measured window (engine
    counters ``prefill_tokens`` / ``prefill_batches``): milliseconds of
    device time per thousand prompt tokens, padding included in the time
    and not in the tokens. The same reading as ``prefill_device_ms_per_ktok``,
    for a closed loop: there a prefill's cost is felt as a gap in every
    running stream (``itl_mean_ms``), not as a time to first token."""
    import stats

    trace = ctx["trace"]
    a, b = ctx["metrics_before"], ctx["metrics_after"]
    if not trace or not trace["programs"]["prefill"]["count"]:
        return None
    tokens = stats.counter_delta(a, b, "dlti_prefill_tokens")
    calls = stats.counter_delta(a, b, "dlti_prefill_batches")
    if not tokens or not calls:
        return None
    prefill = trace["programs"]["prefill"]
    per_call_s = prefill["total_s"] / prefill["count"]
    return 1e3 * per_call_s / (tokens / calls / 1e3)
