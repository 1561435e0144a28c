"""Per-layer metric ``ttft_p50_ms``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "ttft_p50_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "server and admission (serving/server.py, engine admit)"
MOVES = "ttft_mean_ms"
SOURCE = "host_clock"


def read(ctx):
    """The median of the times to first token whose mean is
    ``ttft_mean_ms`` (from the due time, requests due in the window)."""
    return (ctx.get("latencies") or {}).get(NAME)
