"""Per-layer metric ``idle_ms_per_step.admit``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "idle_ms_per_step.admit"
UNIT = "ms/step"
BETTER = "lower"
LAYER = "server and admission (serving/server.py, engine admit)"
MOVES = "itl_mean_ms"
SOURCE = "program_span"


def read(ctx):
    """Device idle time under ``engine/admit`` and everything nested in it
    (scheduling, ``engine/prefill_group`` with its launch and wait, first
    tokens) and under ``engine/prefill_chunks``, per execution of the
    decode program in the traced window."""
    return attribute_idle.idle_ms_per_step(ctx, "admit")
