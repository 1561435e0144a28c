"""Per-layer metric ``handler_cpu_us_per_token``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import host_account

NAME = "handler_cpu_us_per_token"
UNIT = "us"
BETTER = "lower"
LAYER = "server and admission (serving/server.py, engine admit)"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    """Thread CPU microseconds a streaming handler pays an event
    (detokenise the answer so far, scan for stop strings, write the frame):
    d ``sse_handler_cpu_seconds`` over d ``sse_events``. A handler thread
    reads its CPU clock at every 64th event it streams and books its running
    total since the last read, for those 64 events (whole blocks alone: a
    response's last events, short of a block, are in neither series), so
    the clock's 10 ms tick on the chip's host costs one tick a thread and is
    never scaled. Grows with the answer's length while the detokenisation
    is quadratic."""
    import stats

    a, b = host_account.scrapes(ctx)
    cpu = stats.counter_delta(a, b, "dlti_sse_handler_cpu_seconds_total")
    events = stats.counter_delta(a, b, "dlti_sse_events_total")
    if cpu is None or not events:
        return None
    return 1e6 * cpu / events
