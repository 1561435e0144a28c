"""Per-layer metric ``mhc_device_ms_per_ktok``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "mhc_device_ms_per_ktok"
UNIT = "ms/ktok"
BETTER = "lower"
LAYER = "model (models/latent.py, models/hyper.py)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """Device time a prefill call spends on the stream maps and the two
    mixes, per thousand prompt tokens: the operations of ``jit_prefill``
    that the trace puts under the scopes ``dlti_mhc_map`` and
    ``dlti_mhc_mix`` (``scope_time``: the union of their intervals a scope,
    a call in the mean), over the mean prompt tokens of a prefill call in
    the window (d ``prefill_tokens`` / d ``prefill_batches``), as
    ``prefill_device_ms_per_ktok.closed`` divides the whole program. A
    fusion counts under the scope of the operation the compiler named it
    after. None where the trace's operations carry no scope, or without the
    counters."""
    import scope_time
    import stats

    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    tokens = stats.counter_delta(a, b, "dlti_prefill_tokens")
    calls = stats.counter_delta(a, b, "dlti_prefill_batches")
    if not tokens or not calls:
        return None
    per_call_s = scope_time.scope_s_per_call(ctx, "prefill", "dlti_mhc_")
    if per_call_s is None:
        return None
    return 1e3 * per_call_s / (tokens / calls / 1e3)
