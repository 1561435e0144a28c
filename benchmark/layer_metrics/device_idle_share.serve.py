"""Per-layer metric ``device_idle_share.serve``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "device_idle_share.serve"
UNIT = "%"
BETTER = "lower"
LAYER = "device"
MOVES = "output_tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    """1 - union of device-operation intervals / traced window."""
    trace = ctx["trace"]
    if not trace or trace["idle_share"] is None:
        return None
    return 100.0 * trace["idle_share"]
