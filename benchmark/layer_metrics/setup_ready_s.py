"""Per-layer metric ``setup_ready_s``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "setup_ready_s"
UNIT = "s"
BETTER = "lower"
LAYER = "server start-up (scripts/serve.py)"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(ctx):
    """``dlti_startup_ready_seconds`` as the window opened: process start
    (the kernel's record) to the server's socket bound, counted by the
    program itself. The rest of ``setup_s`` is the harness's warm-up of
    the prefill shapes and its check requests. None for a program
    without the gauge."""
    return attribute_idle.startup_seconds(ctx, ["dlti_startup_ready_seconds"])
