"""Per-layer metric ``idle_ms_per_step.server``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "idle_ms_per_step.server"
UNIT = "ms/step"
BETTER = "lower"
LAYER = "server and admission (serving/server.py, engine admit)"
MOVES = "itl_mean_ms"
SOURCE = "program_span"


def read(ctx):
    """Device idle time while the innermost open span of the stepper thread
    is a ``server/*`` one (taking the lock, draining events to the
    handlers' queues, parked without work, ``server/step`` outside the
    engine's own spans), per execution of the decode program in the
    traced window."""
    return attribute_idle.idle_ms_per_step(ctx, "server")
