"""Per-layer metric ``itl_p95_ms``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "itl_p95_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "executor, decode program (serving/engine.py EngineExecutor)"
MOVES = "itl_mean_ms"
SOURCE = "host_clock"


def read(ctx):
    """The 95th percentile of every gap between streamed tokens that ended
    in the window: a decode step with a prefill squeezed in. Recorded and
    not gated - in the chat cell it spreads by up to 8.6 % from run to run
    (my chip runs, PR 23), and a bound may be at most 0.10, which a check would refuse as too
    tight over such runs."""
    return (ctx.get("latencies") or {}).get(NAME)
