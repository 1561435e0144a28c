"""Per-layer metric ``queue_wait_mean_ms``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "queue_wait_mean_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "server and admission (serving/server.py, engine admit)"
MOVES = "ttft_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    """``dlti_request_queue_time_seconds``: sum over count of the requests
    admitted during the window (submit to admission, the engine's clock)."""
    a, b = ctx["metrics_before"], ctx["metrics_after"]
    name = "dlti_request_queue_time_seconds"
    if name + "_count" not in b:
        return None
    n = b[name + "_count"] - a.get(name + "_count", 0.0)
    if n <= 0:
        return None
    return 1e3 * (b[name + "_sum"] - a.get(name + "_sum", 0.0)) / n
