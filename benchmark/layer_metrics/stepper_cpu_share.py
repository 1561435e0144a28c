"""Per-layer metric ``stepper_cpu_share``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "stepper_cpu_share"
UNIT = "%"
BETTER = "higher"
LAYER = "server and admission (serving/server.py, engine admit)"
MOVES = "itl_mean_ms"
SOURCE = "program_span"


def read(ctx):
    """CPU time over wall time of the stepper thread's ``server/step`` spans
    inside the traced window, from the tracer's ring export (``cpu_us`` of
    each span). The rest of the wall time the thread was off the CPU:
    waiting for the device, for ``_work``, or for the interpreter lock
    behind the handler threads."""
    got = attribute_idle.for_run(ctx)
    cpu = got and got.get("stepper_cpu")
    return 100.0 * cpu["share"] if cpu else None
