"""Per-layer metric ``itl_p50_ms``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "itl_p50_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "executor, decode program (serving/engine.py EngineExecutor)"
MOVES = "itl_mean_ms"
SOURCE = "host_clock"


def read(ctx):
    """The median of the gaps whose mean is ``itl_mean_ms``: every gap
    between streamed tokens that ended in the window (a decode step with no
    prefill squeezed in, as the client sees it)."""
    return (ctx.get("latencies") or {}).get(NAME)
