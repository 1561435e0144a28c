"""Per-layer metric ``idle_attributed_share.serve``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "idle_attributed_share.serve"
UNIT = "%"
BETTER = "higher"
LAYER = "device"
MOVES = "itl_mean_ms"
SOURCE = "program_span"


def read(ctx):
    """Device idle time of the traced window that a span of the stepper
    thread (the one that runs ``server/step``) covers, over all of it:
    what the ``idle_ms_per_step.*`` split may be trusted for. None for a
    program that annotates no spans."""
    return attribute_idle.attributed_share(ctx, "server/step")
