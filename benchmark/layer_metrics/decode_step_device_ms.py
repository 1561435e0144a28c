"""Per-layer metric ``decode_step_device_ms``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "decode_step_device_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "executor, decode program (serving/engine.py EngineExecutor)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """Device time of the decode program per execution while the trace was
    taken: total over count, both from the trace (one execution is one
    decode step at the default ``steps_per_sync``). The engine's counters
    cannot be cut to the traced span: the profiler's start and stop take
    seconds of their own, during which the engine keeps stepping."""
    trace = ctx["trace"]
    if not trace or not trace["programs"]["decode"]["count"]:
        return None
    decode = trace["programs"]["decode"]
    return 1e3 * decode["total_s"] / decode["count"]
