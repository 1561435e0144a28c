"""Per-layer metric ``ttft_p95_ms``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "ttft_p95_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "server and admission (serving/server.py, engine admit)"
MOVES = "ttft_mean_ms"
SOURCE = "host_clock"


def read(ctx):
    """The 95th percentile of the times to first token (from the due time,
    requests due in the window): the tail a user feels, recorded and not
    gated - over 123 requests it spreads by up to 7.5 % from run to run
    (my chip runs, PR 23), and a bound may be at most 0.10, which a check would refuse as too
    tight over such runs."""
    return (ctx.get("latencies") or {}).get(NAME)
